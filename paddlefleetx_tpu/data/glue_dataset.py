"""GLUE task datasets (reference ppfleetx/data/dataset/glue_dataset.py:48-841:
CoLA / SST2 / MRPC / STSB / QQP / MNLI / QNLI / RTE / WNLI).

Reads the standard GLUE TSV layout from a local directory (``root/train.tsv``
/ ``dev.tsv``); column positions and label maps per task follow the public
GLUE release (same as the reference's processors).  Features come in two
styles:

  - ``gpt``: single token stream ``text_a [sep] text_b``, last-token
    classification (GPTForSequenceClassification path)
  - ``bert``: ``[CLS] a [SEP] b [SEP]`` with token-type ids (Ernie path)

Labels: int64 class index, or float32 for the STS-B regression task.
"""

from __future__ import annotations

import csv
import os
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from paddlefleetx_tpu.utils.registry import DATASETS

# task -> (sentence columns (train), label column (train), label map, num_classes)
# column layouts of the public GLUE TSVs
_TASKS = {
    "cola": {"cols": (3,), "label": 1, "labels": ["0", "1"], "skip_header": False},
    "sst2": {"cols": (0,), "label": 1, "labels": ["0", "1"], "skip_header": True},
    "mrpc": {"cols": (3, 4), "label": 0, "labels": ["0", "1"], "skip_header": True},
    "stsb": {"cols": (7, 8), "label": 9, "labels": None, "skip_header": True},
    "qqp": {"cols": (3, 4), "label": 5, "labels": ["0", "1"], "skip_header": True},
    "mnli": {
        "cols": (8, 9),
        "label": -1,
        "labels": ["contradiction", "entailment", "neutral"],
        "skip_header": True,
    },
    "qnli": {
        "cols": (1, 2),
        "label": -1,
        "labels": ["entailment", "not_entailment"],
        "skip_header": True,
    },
    "rte": {
        "cols": (1, 2),
        "label": -1,
        "labels": ["entailment", "not_entailment"],
        "skip_header": True,
    },
    "wnli": {"cols": (1, 2), "label": -1, "labels": ["0", "1"], "skip_header": True},
}

# default eval metric per task (reference finetune yamls)
TASK_METRICS = {
    "cola": {"name": "Mcc"},
    "sst2": {"name": "Accuracy"},
    "mrpc": {"name": "AccuracyAndF1"},
    "stsb": {"name": "PearsonAndSpearman"},
    "qqp": {"name": "AccuracyAndF1"},
    "mnli": {"name": "Accuracy"},
    "qnli": {"name": "Accuracy"},
    "rte": {"name": "Accuracy"},
    "wnli": {"name": "Accuracy"},
}


def _read_tsv(path: str, skip_header: bool) -> List[List[str]]:
    with open(path, encoding="utf-8") as f:
        reader = csv.reader(f, delimiter="\t", quotechar=None)
        rows = list(reader)
    return rows[1:] if skip_header else rows


@DATASETS.register("GLUEDataset")
class GLUEDataset:
    def __init__(
        self,
        task: str,
        root: Optional[str] = None,
        tokenizer=None,
        examples: Optional[List[Tuple[List[str], Optional[str]]]] = None,
        max_seq_len: int = 128,
        style: str = "gpt",
        mode: str = "Train",
        pad_id: int = 0,
        cls_id: int = 1,
        sep_id: int = 2,
        **_,
    ):
        task = task.lower().replace("-", "")
        if task not in _TASKS:
            raise ValueError(f"unknown GLUE task {task!r}; known {sorted(_TASKS)}")
        self.task = task
        spec = _TASKS[task]
        self.is_regression = spec["labels"] is None
        self.num_classes = 1 if self.is_regression else len(spec["labels"])
        self.max_seq_len = int(max_seq_len)
        self.style = style
        self.tokenizer = tokenizer
        self.pad_id, self.cls_id, self.sep_id = pad_id, cls_id, sep_id

        if examples is None:
            fname = "train.tsv" if mode == "Train" else "dev.tsv"
            if task == "mnli" and mode != "Train":
                fname = "dev_matched.tsv"
            rows = _read_tsv(os.path.join(root, fname), spec["skip_header"])
            examples = []
            for row in rows:
                try:
                    texts = [row[c] for c in spec["cols"]]
                    label = row[spec["label"]]
                except IndexError:
                    continue  # malformed line
                examples.append((texts, label))
        self.examples = examples
        label_map = (
            None
            if self.is_regression
            else {name: i for i, name in enumerate(spec["labels"])}
        )
        self._features = [
            self._featurize(texts, label, label_map) for texts, label in self.examples
        ]

    def _encode(self, text) -> List[int]:
        if self.tokenizer is not None:
            return self.tokenizer.encode(text)
        if isinstance(text, str):  # no tokenizer: hashed-word fallback (tests)
            # crc32, not hash(): Python salts str hashes per process, and
            # the ids have to be the same in every run
            return [zlib.crc32(w.encode("utf-8")) % 30000 + 10 for w in text.split()]
        return list(text)  # already token ids

    def _featurize(self, texts, label, label_map) -> Dict[str, np.ndarray]:
        encoded = [self._encode(t) for t in texts]
        L = self.max_seq_len
        if self.style == "bert":
            a = encoded[0]
            b = encoded[1] if len(encoded) > 1 else []
            budget = L - (3 if b else 2)
            while len(a) + len(b) > budget:  # truncate longest-first
                if len(a) >= len(b):
                    a = a[:-1]
                else:
                    b = b[:-1]
            ids = [self.cls_id] + a + [self.sep_id] + (b + [self.sep_id] if b else [])
            token_type = [0] * (len(a) + 2) + [1] * (len(b) + 1 if b else 0)
            n = len(ids)
            feats = {
                "input_ids": np.asarray(ids + [self.pad_id] * (L - n), np.int64),
                "token_type_ids": np.asarray(token_type + [0] * (L - n), np.int64),
                "attention_mask": np.asarray([1.0] * n + [0.0] * (L - n), np.float32),
            }
        else:  # gpt: plain concatenated stream, right-padded
            ids: List[int] = []
            for i, e in enumerate(encoded):
                if i > 0:
                    ids.append(self.sep_id)
                ids.extend(e)
            ids = ids[: L - 1] if len(ids) >= L else ids
            n = len(ids)
            feats = {
                "tokens": np.asarray(ids + [self.pad_id] * (L - n), np.int64),
                "position_ids": np.arange(L, dtype=np.int64),
                # index of the last real token: its hidden state classifies
                "cls_position": np.int64(max(n - 1, 0)),
            }
        if self.is_regression:
            feats["labels"] = np.float32(float(label))
        else:
            feats["labels"] = np.int64(
                label_map[label.strip()] if isinstance(label, str) else int(label)
            )
        return feats

    def __len__(self) -> int:
        return len(self._features)

    def __getitem__(self, i: int) -> Dict[str, np.ndarray]:
        return self._features[i]


def write_synthetic_glue_task(
    root: str, task: str = "sst2", n: int = 64, seed: int = 0
) -> str:
    """Write a tiny fake GLUE TSV pair (train/dev) for tests: label-correlated
    token patterns so finetuning is learnable."""
    rng = np.random.default_rng(seed)
    os.makedirs(root, exist_ok=True)
    spec = _TASKS[task.lower().replace("-", "")]
    pos_words = ["good", "great", "excellent", "happy"]
    neg_words = ["bad", "awful", "terrible", "sad"]
    for fname in ("train.tsv", "dev.tsv"):
        with open(os.path.join(root, fname), "w", encoding="utf-8") as f:
            if spec["skip_header"]:
                f.write("header\t" * 10 + "\n")
            for _ in range(n):
                y = int(rng.integers(0, 2))
                words = [
                    str(rng.choice(pos_words if y else neg_words))
                    for _ in range(int(rng.integers(3, 8)))
                ]
                text = " ".join(words)
                if task == "sst2":
                    f.write(f"{text}\t{y}\n")
                elif task == "cola":
                    f.write(f"x\t{y}\tx\t{text}\n")
                else:
                    raise NotImplementedError(f"synthetic writer for {task}")
    return root
