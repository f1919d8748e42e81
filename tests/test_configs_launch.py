"""Every registered module has >=1 config YAML that tools/train.py can
drive (VERDICT r1 item 7): cheap validation (config -> process -> module
build) for all family configs, plus real 2-3 step CLI-equivalent training
for the synthetic-data families on the 8-device CPU mesh."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_CONFIGS = [
    # (config path, num_devices)
    ("configs/gpt/pretrain_gpt_345M_single.yaml", 1),
    ("configs/gpt/pretrain_gpt_1.3B_mp8.yaml", 8),
    ("configs/gpt/pretrain_gpt_6.7B_sharding16.yaml", 16),
    ("configs/gpt/pretrain_gpt_175B_mp8_pp16.yaml", 128),
    ("configs/gpt/finetune_gpt_345M_glue.yaml", 1),
    ("configs/gpt/qat_gpt_345M_mp8.yaml", 8),
    ("configs/ernie/pretrain_ernie_base.yaml", 1),
    ("configs/ernie/pretrain_ernie_175B_mp8_pp16.yaml", 128),
    ("configs/t5/pretrain_t5_base.yaml", 1),
    ("configs/debertav2/pretrain_debertav2_base.yaml", 1),
    ("configs/imagen/imagen_text2im_64_base.yaml", 1),
    ("configs/protein/helixfold_initial.yaml", 1),
    ("configs/protein/helixfold_tiny_smoke.yaml", 1),
    ("configs/vis/vit/ViT_base_patch16_224_pt_in1k_1n8c_dp.yaml", 8),
    ("configs/vis/vit/ViT_tiny_ci_synthetic_1n8c_dp.yaml", 8),
    ("configs/vis/moco/mocov1_pt_in1k_1n8c.yaml", 8),
    ("configs/vis/moco/mocov2_pt_in1k_1n8c.yaml", 8),
    ("configs/vis/moco/moco_lincls_in1k_1n8c.yaml", 8),
    ("configs/vis/resnet/resnet50_in1k_1n8c.yaml", 8),
    ("configs/multimodal/clip/clip_vitb16_pt_1n8c.yaml", 8),
]


def test_project_launchers_reference_real_files():
    """Every projects/*.sh launcher points at a config and tool that exist
    (reference ships projects/<model>/*.sh wrappers, SURVEY.md §1.1)."""
    import glob
    import re

    scripts = glob.glob(os.path.join(REPO, "projects", "*", "*.sh"))
    assert len(scripts) >= 15
    for sh in scripts:
        with open(sh) as f:
            text = f.read()
        m = re.search(r"python (\S+)(?:\s+-c\s+(\S+))?", text)
        assert m, f"{sh}: no python invocation"
        assert os.path.exists(os.path.join(REPO, m.group(1))), f"{sh}: {m.group(1)}"
        if m.group(2):
            assert os.path.exists(os.path.join(REPO, m.group(2))), f"{sh}: {m.group(2)}"


@pytest.mark.parametrize("path,ndev", ALL_CONFIGS)
def test_config_loads_and_module_builds(path, ndev):
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.utils.config import get_config

    cfg = get_config(os.path.join(REPO, path), num_devices=ndev)
    module = build_module(cfg)
    assert hasattr(module, "loss_fn")


@pytest.mark.parametrize("path,ndev", ALL_CONFIGS)
def test_config_optimizer_builds(path, ndev):
    """build_optimizer accepts every shipped Optimizer block — catches
    config-schema drift the module-build smoke can't (the T5 scalar
    grad_clip crash lived here undetected until round 4)."""
    from paddlefleetx_tpu.optims.optimizer import build_optimizer
    from paddlefleetx_tpu.utils.config import get_config

    cfg = get_config(os.path.join(REPO, path), num_devices=ndev)
    tx, schedule = build_optimizer(cfg.Optimizer)
    assert tx is not None and callable(schedule)


def _run_train(config, overrides, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    env["PFX_PLATFORM"] = "cpu"
    cmd = [sys.executable, os.path.join(REPO, "tools", "train.py"), "-c",
           os.path.join(REPO, config)]
    for o in overrides:
        cmd += ["-o", o]
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "step " in out.stderr or "step " in out.stdout


@pytest.mark.slow
def test_moco_synthetic_trains_via_cli():
    _run_train(
        "configs/vis/moco/mocov2_pt_in1k_1n8c.yaml",
        [
            "Global.global_batch_size=16", "Global.local_batch_size=2",
            "Global.micro_batch_size=2",
            "Engine.max_steps=2", "Engine.logging_freq=1", "Engine.eval_freq=0",
            "Engine.save_load.save_steps=0", "Engine.mix_precision.enable=False",
            "Model.K=64", "Model.dim=16", "Model.base_encoder=resnet18",
            "Data.Train.dataset.name=ContrastiveLearningDataset",
            "Data.Train.dataset.cls_label_path=null",
            "Data.Train.dataset.root=null",
            "Data.Train.dataset.num_samples=32",
            "Data.Train.dataset.image_size=32",
        ],
    )


@pytest.mark.slow
def test_clip_synthetic_trains_via_cli(tmp_path):
    from paddlefleetx_tpu.data.multimodal_dataset import (
        write_synthetic_image_text_corpus,
    )
    from paddlefleetx_tpu.data.tokenizers.t5_tokenizer import T5Tokenizer

    corpus = write_synthetic_image_text_corpus(
        str(tmp_path / "corpus.jsonl"), n=16, image_size=32
    )
    tok = T5Tokenizer.from_tiny_corpus(["a tiny synthetic image"])
    tok.save(str(tmp_path / "vocab.json"))
    _run_train(
        "configs/multimodal/clip/clip_vitb16_pt_1n8c.yaml",
        [
            "Global.global_batch_size=8", "Global.local_batch_size=1",
            "Global.micro_batch_size=1",
            "Engine.max_steps=2", "Engine.logging_freq=1", "Engine.eval_freq=0",
            "Engine.save_load.save_steps=0", "Engine.mix_precision.enable=False",
            "Model.projection_dim=16", "Model.image_size=32", "Model.patch_size=8",
            "Model.vision_hidden_size=32", "Model.vision_layers=2",
            "Model.vision_heads=4", "Model.text_hidden_size=32",
            "Model.text_layers=2", "Model.text_heads=4", "Model.max_text_len=16",
            f"Model.vocab_size={max(tok.vocab_size, 64)}",
            f"Data.Train.dataset.input_path={corpus}",
            "Data.Train.dataset.image_size=32",
            "Data.Train.dataset.max_seq_len=16",
            f"Data.Train.dataset.tokenizer_vocab={tmp_path}/vocab.json",
        ],
    )


@pytest.mark.slow
def test_resnet_synthetic_trains_via_cli():
    _run_train(
        "configs/vis/resnet/resnet50_in1k_1n8c.yaml",
        [
            "Global.global_batch_size=16", "Global.local_batch_size=2",
            "Global.micro_batch_size=2",
            "Engine.max_steps=2", "Engine.logging_freq=1", "Engine.eval_freq=0",
            "Engine.save_load.save_steps=0", "Engine.mix_precision.enable=False",
            "Model.depth=18", "Model.num_classes=8",
            "Data.Train.dataset.name=SyntheticClsDataset",
            "Data.Train.dataset.num_samples=32",
            "Data.Train.dataset.image_size=32",
            "Data.Train.dataset.num_classes=8",
            "Data.Eval.dataset.name=SyntheticClsDataset",
            "Data.Eval.dataset.num_samples=8",
            "Data.Eval.dataset.image_size=32",
            "Data.Eval.dataset.num_classes=8",
        ],
    )


# ---------------------------------------------------------------------------
# download utils + no-engine examples
# ---------------------------------------------------------------------------


def test_cached_path_local_and_md5(tmp_path):
    from paddlefleetx_tpu.utils.download import cached_path, check_md5, md5file

    f = tmp_path / "artifact.bin"
    f.write_bytes(b"hello weights")
    p = cached_path(str(f))
    assert p == str(f)
    digest = md5file(p)
    assert check_md5(p, digest)
    assert not check_md5(p, "0" * 32)
    with pytest.raises(IOError):
        cached_path(str(f), md5sum="0" * 32)
    with pytest.raises(FileNotFoundError):
        cached_path(str(tmp_path / "missing.bin"))


def test_download_retries_and_atomic(tmp_path, monkeypatch):
    """A flaky 'transport' fails twice then succeeds; the cache file appears
    atomically with the right contents."""
    import io
    import urllib.request

    from paddlefleetx_tpu.utils import download as dl

    calls = {"n": 0}

    def fake_urlopen(url):
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError("flaky network")

        class Ctx:
            def __enter__(self):
                return io.BytesIO(b"payload")

            def __exit__(self, *a):
                return False

        return Ctx()

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    out = dl.cached_path(
        "http://example.invalid/weights.bin", cache_dir=str(tmp_path)
    )
    assert open(out, "rb").read() == b"payload"
    assert calls["n"] == 3
    # cached: no further transport calls
    out2 = dl.cached_path(
        "http://example.invalid/weights.bin", cache_dir=str(tmp_path)
    )
    assert out2 == out and calls["n"] == 3


def test_download_sha256_quarantines_and_refetches(tmp_path, monkeypatch):
    """A cached artifact whose sha256 stops matching is quarantined
    (*.corrupt) and re-fetched; a mirror that keeps serving a bad body
    exhausts the retry loudly naming the download."""
    import hashlib
    import io
    import urllib.request

    from paddlefleetx_tpu.utils import download as dl

    good = b"good weights"
    good_sha = hashlib.sha256(good).hexdigest()
    serve = {"body": good, "n": 0}

    def fake_urlopen(url):
        serve["n"] += 1

        class Ctx:
            def __enter__(self):
                return io.BytesIO(serve["body"])

            def __exit__(self, *a):
                return False

        return Ctx()

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    monkeypatch.setenv("PFX_RETRY_BACKOFF", "0.0")
    url = "http://example.invalid/model.bin"
    out = dl.cached_path(url, cache_dir=str(tmp_path), sha256sum=good_sha)
    assert open(out, "rb").read() == good and serve["n"] == 1

    # rot the cached file: next resolve quarantines + re-fetches
    with open(out, "wb") as f:
        f.write(b"bit-rotted")
    out2 = dl.cached_path(url, cache_dir=str(tmp_path), sha256sum=good_sha)
    assert out2 == out and open(out, "rb").read() == good
    assert serve["n"] == 2
    assert (tmp_path / "model.bin.corrupt").exists()

    # mirror serves garbage forever: retry exhausts LOUDLY, nothing lands
    serve["body"] = b"always wrong"
    with open(out, "wb") as f:
        f.write(b"bit-rotted again")
    with pytest.raises(RuntimeError, match="download"):
        dl.cached_path(url, cache_dir=str(tmp_path), sha256sum=good_sha)
    assert not (tmp_path / "model.bin").exists()  # bad body never cached


@pytest.mark.slow
def test_no_engine_examples_run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    env["PFX_PLATFORM"] = "cpu"
    for script, extra in (
        ("examples/transformer/train_no_engine.py", []),
        ("examples/transformer/generate_no_engine.py", []),
        ("examples/transformer/long_context_ring.py",
         ["--seq", "512", "--steps", "1", "--hidden", "64"]),
    ):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, script)] + extra,
            capture_output=True, text=True, timeout=420, cwd=REPO, env=env,
        )
        assert out.returncode == 0, (script, out.stderr[-1500:])


def test_file_utils_roundtrip(tmp_path):
    import tarfile
    import zipfile

    from paddlefleetx_tpu.utils.file import parse_csv, untar, unzip

    (tmp_path / "a.txt").write_text("hello")
    zp = str(tmp_path / "arch.zip")
    with zipfile.ZipFile(zp, "w") as z:
        z.write(tmp_path / "a.txt", "a.txt")
    out = unzip(zp, out_dir=str(tmp_path / "unz"))
    assert (tmp_path / "unz" / "a.txt").read_text() == "hello"

    tp = str(tmp_path / "arch.tar.gz")
    with tarfile.open(tp, "w:gz") as t:
        t.add(tmp_path / "a.txt", "a.txt")
    untar(tp, out_dir=str(tmp_path / "unt"))
    assert (tmp_path / "unt" / "a.txt").read_text() == "hello"

    (tmp_path / "t.csv").write_text("k,v\nx,1\ny,2\n")
    rows = parse_csv(str(tmp_path / "t.csv"))
    assert rows == [{"k": "x", "v": "1"}, {"k": "y", "v": "2"}]


def test_device_identity_names_what_jax_found():
    """The three fields both entry points log at start and /healthz
    carries — as JAX reports them, never as the config wishes."""
    import jax

    from paddlefleetx_tpu.utils.device import device_identity

    ident = device_identity()
    assert ident == {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    assert ident["platform"] == "cpu"  # this suite is pinned to the CPU


@pytest.mark.slow
def test_export_then_inference_cli(tmp_path):
    """tools/export.py -> tools/inference.py chain on the CPU mesh
    (reference deploy path: export -> InferenceEngine predict)."""
    from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus

    data = tmp_path / "data"
    data.mkdir()
    write_synthetic_corpus(str(data / "corp"), vocab_size=128, num_docs=16)
    common = [
        "Model.num_layers=2", "Model.hidden_size=64",
        "Model.num_attention_heads=4", "Model.vocab_size=128",
        "Model.max_position_embeddings=32",
        "Global.global_batch_size=16", "Global.local_batch_size=2",
        "Global.micro_batch_size=2",
        f"Data.Train.dataset.input_dir={data}", "Data.Train.dataset.max_seq_len=32",
        f"Engine.save_load.output_dir={tmp_path / 'out'}",
    ]
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    env["PFX_PLATFORM"] = "cpu"

    def run(tool, extra):
        cmd = [sys.executable, os.path.join(REPO, "tools", tool),
               "-c", os.path.join(REPO, "configs/gpt/pretrain_gpt_345M_single.yaml")]
        for o in common + extra:
            cmd += ["-o", o]
        out = subprocess.run(cmd, capture_output=True, text=True, timeout=540,
                             cwd=REPO, env=env)
        assert out.returncode == 0, (tool, out.stderr[-2000:])
        return out.stdout + out.stderr

    run("export.py", [])
    assert (tmp_path / "out" / "inference" / "model.stablehlo").exists()
    log = run("inference.py", [
        f"Inference.model_dir={tmp_path / 'out' / 'inference'}",
        "Inference.max_seq_len=32",
    ])
    assert "inference ok" in log


@pytest.mark.slow
def test_gpt_task_clis(tmp_path):
    """tasks/gpt/{generation,inference}.py run end-to-end on the tiny
    config (reference tasks/gpt parity: no-engine generation demo +
    engine-mode inference demo)."""
    cfg = tmp_path / "tiny.yaml"
    cfg.write_text(
        """Global:
  global_batch_size: 8
  seed: 3
Engine:
  mix_precision:
    enable: False
  save_load:
    save_steps: 0
Model:
  module: GPTModule
  vocab_size: 96
  hidden_size: 32
  num_layers: 2
  num_attention_heads: 4
  max_position_embeddings: 128
  dtype: float32
Distributed: {}
Optimizer:
  name: FusedAdamW
  lr:
    name: Constant
    learning_rate: 0.001
Generation:
  max_dec_len: 8
  decode_strategy: greedy_search
  pad_to_multiple: 16
  eos_token_id: 95
  pad_token_id: 0
"""
    )
    env = dict(os.environ)
    env["PFX_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    for script in ("tasks/gpt/generation.py", "tasks/gpt/inference.py"):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, script), "-c", str(cfg)],
            capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
        )
        assert out.returncode == 0, (script, out.stderr[-2000:])
        assert "enerat" in out.stdout + out.stderr, script  # Generated/generation


@pytest.mark.slow
def test_crash_and_auto_resume_e2e(tmp_path):
    """Fault injection through the real CLI (SURVEY §5.3: recovery =
    checkpoint/resume): SIGKILL tools/train.py mid-run after a checkpoint
    lands, relaunch with auto_resume — training continues from the newest
    complete step dir and finishes."""
    import signal
    import time as _time

    from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus

    data = tmp_path / "data"
    data.mkdir()
    write_synthetic_corpus(str(data / "corp"), vocab_size=128, num_docs=16)
    out = tmp_path / "out"
    common = [
        "Model.num_layers=2", "Model.hidden_size=32",
        "Model.num_attention_heads=4", "Model.vocab_size=128",
        "Model.max_position_embeddings=32",
        "Global.global_batch_size=8", "Global.local_batch_size=8",
        "Global.micro_batch_size=8",
        "Engine.max_steps=16", "Engine.logging_freq=1", "Engine.eval_freq=0",
        "Engine.mix_precision.enable=False",
        "Engine.save_load.save_steps=2",
        "Engine.save_load.auto_resume=True",
        f"Engine.save_load.output_dir={out}",
        f"Data.Train.dataset.input_dir={data}", "Data.Train.dataset.max_seq_len=32",
    ]
    env = dict(os.environ)
    env["PFX_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    cmd = [sys.executable, os.path.join(REPO, "tools", "train.py"), "-c",
           os.path.join(REPO, "configs/gpt/pretrain_gpt_345M_single.yaml")]
    for o in common:
        cmd += ["-o", o]

    # run 1: kill -9 once the first checkpoint is complete
    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = _time.time() + 300
    try:
        while _time.time() < deadline:
            if (out / "step_2" / "meta.json").exists():
                break
            if proc.poll() is not None:
                raise AssertionError(f"train exited early rc={proc.returncode}")
            # tight poll: the kill must land well before the remaining 14
            # steps (+7 checkpoint saves) finish
            _time.sleep(0.05)
        else:
            raise AssertionError("no checkpoint appeared before the deadline")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        # the kill must interrupt a LIVE run: if all 16 steps already
        # finished, run 2 would resume at step_16, train zero steps, and
        # this test would pass without exercising the crash path
        assert not (out / "step_16" / "meta.json").exists(), (
            "run 1 completed before the kill — crash path not exercised; "
            "slow the run down (more steps or a bigger model)"
        )
    finally:
        if proc.poll() is None:
            proc.kill()

    # run 2: auto-resume from the newest complete checkpoint, finish
    run2 = subprocess.run(cmd, capture_output=True, text=True, timeout=540,
                          cwd=REPO, env=env)
    assert run2.returncode == 0, run2.stderr[-2000:]
    log = run2.stdout + run2.stderr
    assert "auto_resume: found" in log
    assert (out / "step_16" / "meta.json").exists(), os.listdir(out)
