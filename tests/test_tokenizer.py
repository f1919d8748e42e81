"""Tokenizer tests with a tiny constructed BPE vocab; round-trip always holds
regardless of merges (byte-level)."""

import json

import pytest

from paddlefleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer, bytes_to_unicode


@pytest.fixture
def tok(tmp_path):
    b2u = bytes_to_unicode()
    # base vocab: all 256 byte symbols + a couple of merges + eos
    symbols = [b2u[b] for b in range(256)]
    merges = [("h", "e"), ("l", "l"), ("he", "ll"), ("hell", "o")]
    for a, b in merges:
        symbols.append(a + b)
    symbols.append("<|endoftext|>")
    vocab = {s: i for i, s in enumerate(dict.fromkeys(symbols))}
    (tmp_path / "vocab.json").write_text(json.dumps(vocab))
    (tmp_path / "merges.txt").write_text(
        "#version: 0.2\n" + "\n".join(f"{a} {b}" for a, b in merges)
    )
    return GPTTokenizer.from_pretrained(str(tmp_path))


def test_roundtrip(tok):
    for text in ["hello world", "hello", "a b  c\nd", "héllo ☂"]:
        assert tok.decode(tok.encode(text)) == text


def test_merges_applied(tok):
    ids = tok.encode("hello")
    # 'hello' fully merges into one token
    assert len(ids) == 1
    assert tok.decoder[ids[0]] == "hello"


def test_eos(tok):
    assert tok.eos_token_id == tok.encoder["<|endoftext|>"]


# ---------------------------------------------------------------------------
# DebertaV2 sentencepiece-style tokenizer
# ---------------------------------------------------------------------------

from paddlefleetx_tpu.data.tokenizers.debertav2_tokenizer import (  # noqa: E402
    DebertaV2Tokenizer,
)

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "deberta uses disentangled attention",
    "sentencepiece segments words into pieces",
]


@pytest.fixture
def dtok():
    return DebertaV2Tokenizer.from_tiny_corpus(CORPUS)


def test_deberta_special_layout(dtok):
    # [PAD]=0, [CLS]=1, [SEP]=2, [UNK]=3; [MASK] appended at the top
    assert dtok.pad_id == 0
    assert dtok.cls_id == 1
    assert dtok.sep_id == 2
    assert dtok.vocab["[UNK]"] == 3
    assert dtok.mask_id == dtok.vocab_size - 1


def test_deberta_roundtrip(dtok):
    for text in CORPUS:
        enc = dtok.encode(text)
        assert enc["input_ids"][0] == dtok.cls_id
        assert enc["input_ids"][-1] == dtok.sep_id
        assert dtok.decode(enc["input_ids"]) == text


def test_deberta_pair_and_padding(dtok):
    enc = dtok.encode("the quick fox", "the lazy dog", max_length=16, padding=True)
    ids, types, mask = enc["input_ids"], enc["token_type_ids"], enc["attention_mask"]
    assert len(ids) == len(types) == len(mask) == 16
    n_sep = sum(1 for i in ids if i == dtok.sep_id)
    assert n_sep == 2
    first_sep = ids.index(dtok.sep_id)
    assert all(t == 0 for t in types[: first_sep + 1])
    pad_start = mask.index(0)
    assert all(t == 1 for t in types[first_sep + 1 : pad_start] if True)
    assert all(i == dtok.pad_id for i in ids[pad_start:])


def test_deberta_truncation(dtok):
    enc = dtok.encode(
        "the quick brown fox jumps over the lazy dog", max_length=6
    )
    assert len(enc["input_ids"]) == 6
    assert enc["input_ids"][0] == dtok.cls_id
    assert enc["input_ids"][-1] == dtok.sep_id


def test_deberta_save_load_stable(dtok, tmp_path):
    p = str(tmp_path / "deberta_vocab.json")
    dtok.save(p)
    tok2 = DebertaV2Tokenizer.from_file(p)
    text = CORPUS[1]
    assert dtok.encode(text) == tok2.encode(text)


def test_t5_sentinel_descending():
    """extra_id_0 must be the HIGHEST id (reference/HF layout)."""
    from paddlefleetx_tpu.data.tokenizers.t5_tokenizer import T5Tokenizer

    t = T5Tokenizer.from_tiny_corpus(CORPUS, num_extra_ids=10)
    assert t.extra_id(0) == t.vocab_size - 1
    assert t.extra_id(9) == t.vocab_size - 10


def test_native_bpe_matches_python(tok, tmp_path):
    """The C++ merge engine (data/cpp/bpe.cpp) produces exactly the Python
    ids on mixed text, including unicode and whitespace runs."""
    texts = [
        "hello hello world",
        "  spaces\tand\nnewlines  ",
        "unicode: café 你好 \U0001f600!",
        "numbers 12345 and punct!!! ...",
        "hellohellohello",
    ]
    if tok._native is None:
        import pytest

        pytest.skip("no native build available")
    for t in texts:
        fast = tok.encode(t)
        # force pure-Python: temporarily drop the native engine
        native, tok._native = tok._native, None
        tok._id_cache.clear()
        slow = tok.encode(t)
        tok._native = native
        assert fast == slow, (t, fast, slow)
        assert tok.decode(fast) == t


def test_native_bpe_specials_fall_back(tok):
    """Special tokens (not byte-mappable) keep working via the Python path."""
    if tok._native is None:
        import pytest

        pytest.skip("no native build available")
    ids = tok.encode("hello")
    assert tok.decode(ids) == "hello"
    assert tok.eos_token_id is not None


# ---------------------------------------------------------------------------
# ERNIE WordPiece tokenizer
# ---------------------------------------------------------------------------


def test_ernie_tokenizer_roundtrip(tmp_path):
    from paddlefleetx_tpu.data.tokenizers.ernie_tokenizer import ErnieTokenizer

    tok = ErnieTokenizer.from_tiny_corpus(["the quick brown fox jumps", "hello world"])
    enc = tok.encode("the quick fox", "hello world", max_seq_len=16)
    ids, types = enc["input_ids"], enc["token_type_ids"]
    assert ids[0] == tok.cls_token_id and ids.count(tok.sep_token_id) == 2
    assert len(ids) == len(types)
    assert set(types) == {0, 1}
    assert tok.decode(ids) == "the quick fox hello world"

    # wordpiece splits unseen compounds into known pieces
    pieces = tok.tokenize("foxworld")
    assert all(p in tok.vocab for p in pieces) and len(pieces) > 1
    assert tok.decode(tok.convert_tokens_to_ids(pieces)) == "foxworld"

    # save/load
    path = str(tmp_path / "vocab.txt")
    tok.save(path)
    tok2 = ErnieTokenizer.from_file(path)
    assert tok2.encode("the quick fox")["input_ids"] == tok.encode("the quick fox")["input_ids"]

    # punctuation is split into its own token (here OOV -> [UNK]); unknown
    # words collapse to [UNK]
    out = tok.tokenize("the fox, x9z!")
    assert out[0] == "the" and out[1] == "fox"
    assert len(out) == 5  # the, fox, ',', x9z, '!'
    assert tok.unk_token in out
