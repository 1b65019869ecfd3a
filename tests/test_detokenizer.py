"""IncrementalDetokenizer: the windowed push / flush against a re-decode of
the whole id history, delta for delta, for every tokenizer the suite can
build; and the cost: what `decode` is handed for the 1,000th token is the
window, not the history (ids counted, no clock read).
"""

import json
import random

import pytest

from tests.test_native_sp import _write_model
from tests.test_native_tiktoken import _base_entries, _write_vocab
from xllm_service_tpu.tokenizer.native_bpe import try_load as load_bpe
from xllm_service_tpu.tokenizer.native_sp import try_load as load_sp
from xllm_service_tpu.tokenizer.native_tiktoken import try_load as load_tk
from xllm_service_tpu.tokenizer.tokenizer import (
    ByteTokenizer,
    HFTokenizer,
    IncrementalDetokenizer,
)

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello world, hello tokenizer 1234 , and . spaced ! punctuation ?",
    "héllo wörld ünïcode résumé naïve — ✓ 🙂 emoji",
    "    indented   runs\tof\nwhitespace  ",
]
MODEL_VOCAB = 151936  # the benchmark's model emits ids over all of it


class FullRedecode:
    """The detokenizer as it was before the window: the reference."""

    def __init__(self, tok, ids=(), emitted=0):
        self._tok, self._ids, self._emitted = tok, list(ids), emitted

    def push(self, ids):
        self._ids.extend(int(i) for i in ids)
        text = self._tok.decode(self._ids)
        stable_end = len(text)
        while stable_end > self._emitted and text[stable_end - 1] == "�":
            stable_end -= 1
        delta = text[self._emitted:stable_end]
        self._emitted = stable_end
        return delta

    def flush(self):
        text = self._tok.decode(self._ids)
        delta = text[self._emitted:]
        self._emitted = len(text)
        return delta


class Counting:
    """A tokenizer that records how many ids each decode was handed."""

    def __init__(self, tok):
        self._tok, self.handed = tok, []

    def decode(self, ids, skip_special_tokens=True):
        self.handed.append(len(ids))
        return self._tok.decode(ids, skip_special_tokens)


@pytest.fixture(scope="module")
def bpe_dir(tmp_path_factory):
    from tokenizers import Tokenizer as RustTokenizer
    from tokenizers import decoders, models, pre_tokenizers, trainers

    d = tmp_path_factory.mktemp("detok-bpe")
    rt = RustTokenizer(models.BPE())
    rt.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    rt.decoder = decoders.ByteLevel()
    rt.train_from_iterator(CORPUS, trainers.BpeTrainer(
        vocab_size=600,
        special_tokens=["<|endoftext|>", "<|im_start|>", "<|im_end|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    ))
    rt.save(str(d / "tokenizer.json"))
    with open(d / "tokenizer_config.json", "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "eos_token": "<|endoftext|>"}, f)
    return str(d)


@pytest.fixture(scope="module")
def tokenizers_by_name(bpe_dir, tmp_path_factory):
    sp = tmp_path_factory.mktemp("detok-sp")
    pieces = [("<unk>", 0.0, 2), ("<s>", 0.0, 3), ("</s>", 0.0, 3),
              ("▁", -2.0, 1), ("▁hello", -1.0, 1), ("▁world", -1.2, 1)]
    pieces += [(c, -6.0, 1) for c in "abcxyz.,"]
    pieces += [(f"<0x{b:02X}>", -9.0, 6) for b in range(256)]
    _write_model(str(sp), pieces)
    tk = tmp_path_factory.mktemp("detok-tk")
    _write_vocab(str(tk), _base_entries())
    out = {
        "byte": ByteTokenizer(),
        "native_bpe": load_bpe(bpe_dir),
        "hf": HFTokenizer(bpe_dir),
        "native_sp": load_sp(str(sp)),
        "native_tiktoken": load_tk(str(tk)),
    }
    assert all(t is not None for t in out.values()), out
    return out


def _streams(tok, rng):
    """(name, ids) cases: what a model emits and what breaks a decode."""
    v = tok.vocab_size
    yield "model_vocab", [rng.randrange(MODEL_VOCAB) for _ in range(400)]
    yield "own_vocab", [rng.randrange(v) for _ in range(400)]
    text = "".join(rng.choice(CORPUS) for _ in range(4))
    yield "text", tok.encode(text)
    # multi-byte characters one byte a token, and runs of bytes that are
    # no character: through the byte tokenizer's id = byte + 3 for the
    # byte family, through each tokenizer's own ids otherwise
    if isinstance(tok, ByteTokenizer):
        yield "split_chars", tok.encode("✓é🙂—" * 20)
        bad = [0x80 + 3] * 40 + tok.encode("ok") + [0xF0 + 3, 0x9F + 3]
        yield "invalid_run", bad + [0x41 + 3] * 3 + [0xE2 + 3] * 9
    else:
        ids = tok.encode("✓é🙂—" * 10)
        yield "split_chars", ids
        yield "invalid_run", [i for i in ids if rng.random() < 0.6] * 2
    # ids a decode skips between ids it does not (specials, out of table)
    skip = [0, 1, 2, v + 7]
    yield "skipped_ids", [
        rng.choice(skip) if rng.random() < 0.5 else rng.randrange(v)
        for _ in range(300)
    ]


@pytest.mark.parametrize(
    "name", ["byte", "native_bpe", "hf", "native_sp", "native_tiktoken"]
)
def test_windowed_push_is_the_full_redecode_delta_for_delta(
    tokenizers_by_name, name
):
    tok = tokenizers_by_name[name]
    rng = random.Random(4300 + len(name))
    for case, ids in _streams(tok, rng):
        for per_push in (1, 3):  # a plain step; a speculative row
            new, ref = IncrementalDetokenizer(tok), FullRedecode(tok)
            cut = len(ids) // 2
            for at in range(0, len(ids), per_push):
                if at <= cut < at + per_push:
                    # a PD hand-off in mid-stream (and, for the split
                    # characters, in mid-character): (ids, emitted) only
                    sids, emitted = new.export_state()
                    assert (sids, emitted) == (ref._ids, ref._emitted)
                    new = IncrementalDetokenizer.from_state(
                        tok, sids, emitted
                    )
                step = ids[at:at + per_push]
                assert new.push(step) == ref.push(step), (name, case, at)
            assert new.flush() == ref.flush(), (name, case)
            assert new.export_state() == (ref._ids, ref._emitted)
            # and pushes after a flush (no caller does it) still agree,
            # although the flush handed out a held-back run
            for i in ids[:8]:
                assert new.push([i]) == ref.push([i]), (name, case)


def test_the_thousandth_token_decodes_a_window_not_the_history():
    rng = random.Random(43)
    tok = Counting(ByteTokenizer())
    d = IncrementalDetokenizer(tok)
    for _ in range(999):
        d.push([rng.randrange(MODEL_VOCAB)])
    tok.handed.clear()
    d.push([rng.randrange(MODEL_VOCAB)])
    assert tok.handed and sum(tok.handed) <= 64, tok.handed
    # over the whole sequence: a few ids a token, not half the history
    tok.handed.clear()
    d2 = IncrementalDetokenizer(tok)
    for _ in range(1000):
        d2.push([rng.randrange(MODEL_VOCAB)])
    assert sum(tok.handed) < 1000 * 16, sum(tok.handed)
    # an imported history is decoded whole once or twice, then windowed
    ids, emitted = d2.export_state()
    d3 = IncrementalDetokenizer.from_state(tok, ids, emitted)
    for _ in range(20):
        d3.push([0x41 + 3])
    tok.handed.clear()
    d3.push([0x41 + 3])
    assert sum(tok.handed) <= 8, tok.handed
