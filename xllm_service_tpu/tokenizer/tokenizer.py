"""Tokenization tier.

The reference ships three native tokenizer families behind one interface —
a Rust HF-tokenizers FFI crate, sentencepiece, and a tiktoken BPE
(reference: xllm_service/tokenizer/tokenizer.h:28-46,
tokenizer_factory.cpp:9-33, fast_tokenizer.cpp, sentencepiece_tokenizer.cpp,
tiktoken_tokenizer.cpp). On this stack TWO native families cover the
dominant formats — the C++ byte-level BPE core (tokenizer/native_bpe.py,
GPT-2/Llama-3/Qwen style) and the C++ SentencePiece-Unigram core
(tokenizer/native_sp.py, .model protobuf + Viterbi + byte fallback) —
with `transformers.AutoTokenizer` (the same Rust `tokenizers` wheel the
reference binds by hand) as the fallback adapter for everything else; a
deterministic byte-level tokenizer covers tests and benches with no model
files on disk.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence


class Tokenizer:
    """Interface (reference: tokenizer.h:28-46)."""

    def encode(self, text: str) -> List[int]:
        raise NotImplementedError

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        raise NotImplementedError

    def id_to_token(self, token_id: int) -> str:
        raise NotImplementedError

    def token_to_id(self, token: str) -> Optional[int]:
        raise NotImplementedError

    @property
    def vocab_size(self) -> int:
        raise NotImplementedError

    @property
    def eos_token_id(self) -> Optional[int]:
        return None

    @property
    def bos_token_id(self) -> Optional[int]:
        return None

    def token_bytes_table(self, vocab_size: int) -> "Optional[List[bytes]]":
        """Per-id raw bytes for guided decoding (JSON mode). None =
        unsupported for this tokenizer family (guided requests are then
        rejected with a clear error). Ids with no byte surface (specials,
        out-of-table) map to b""."""
        return None


class ByteTokenizer(Tokenizer):
    """UTF-8 byte-level tokenizer: id = byte + 3 (0=pad, 1=bos, 2=eos).

    Deterministic, file-free; the test/bench stand-in for a real model
    tokenizer (SURVEY.md §4: the reference has no such seam and cannot unit
    test its tokenize path without model dirs on disk)."""

    PAD, BOS, EOS = 0, 1, 2
    _OFFSET = 3

    def encode(self, text: str) -> List[int]:
        return [b + self._OFFSET for b in text.encode("utf-8")]

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        # Total over arbitrary ids: a model whose vocab exceeds 259 (e.g. the
        # random-init test models) may emit any id — fold it onto a byte.
        data = bytes(
            (i - self._OFFSET) % 256 for i in ids if i >= self._OFFSET
        )
        return data.decode("utf-8", errors="replace")

    def id_to_token(self, token_id: int) -> str:
        if 0 <= token_id < self._OFFSET:
            return ["<pad>", "<bos>", "<eos>"][token_id]
        return chr((token_id - self._OFFSET) % 256)

    def token_to_id(self, token: str) -> Optional[int]:
        specials = {"<pad>": 0, "<bos>": 1, "<eos>": 2}
        if token in specials:
            return specials[token]
        b = token.encode("utf-8")
        return b[0] + self._OFFSET if len(b) == 1 else None

    @property
    def vocab_size(self) -> int:
        return 256 + self._OFFSET

    @property
    def eos_token_id(self) -> Optional[int]:
        return self.EOS

    @property
    def bos_token_id(self) -> Optional[int]:
        return self.BOS

    def token_bytes_table(self, vocab_size: int) -> "List[bytes]":
        # model vocabs may exceed 259 (random-init test configs): decode
        # folds id onto (id - 3) % 256, so the byte table does too
        out = [b"" for _ in range(vocab_size)]
        for i in range(self._OFFSET, vocab_size):
            out[i] = bytes([(i - self._OFFSET) % 256])
        return out


class HFTokenizer(Tokenizer):
    """Adapter over transformers.AutoTokenizer — the union of the
    reference's Fast (tokenizer.json), SentencePiece, and Tiktoken families.
    Encode/decode on HF fast tokenizers is thread-safe; the slow (Python)
    path is guarded by a lock, replacing the reference's thread-local clones
    (scheduler.cpp:166-169)."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(path, trust_remote_code=False)
        self._lock = threading.Lock() if not self._tok.is_fast else None

    def _guarded(self, fn):
        if self._lock is None:
            return fn()
        with self._lock:
            return fn()

    def encode(self, text: str) -> List[int]:
        return self._guarded(lambda: self._tok.encode(text, add_special_tokens=False))

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        return self._guarded(
            lambda: self._tok.decode(list(ids), skip_special_tokens=skip_special_tokens)
        )

    def id_to_token(self, token_id: int) -> str:
        return self._guarded(lambda: self._tok.convert_ids_to_tokens(token_id)) or ""

    def token_to_id(self, token: str) -> Optional[int]:
        tid = self._guarded(lambda: self._tok.convert_tokens_to_ids(token))
        return None if tid == self._tok.unk_token_id and token != self._tok.unk_token else tid

    @property
    def vocab_size(self) -> int:
        return len(self._tok)

    @property
    def eos_token_id(self) -> Optional[int]:
        return self._tok.eos_token_id

    @property
    def bos_token_id(self) -> Optional[int]:
        return self._tok.bos_token_id

    @property
    def hf(self):
        return self._tok

    def token_bytes_table(self, vocab_size: int) -> "Optional[List[bytes]]":
        """Byte surfaces via the tokenizer's own convention: GPT-2-style
        byte-level vocabs map through the bytes_to_unicode table;
        SentencePiece pieces map '\u2581' to space and '<0xNN>' byte
        tokens to their byte; specials map to b""."""
        # GPT-2 byte-level unicode->byte inverse table
        bs = (
            list(range(0x21, 0x7F)) + list(range(0xA1, 0xAD))
            + list(range(0xAE, 0x100))
        )
        cs = bs[:]
        n = 0
        for b in range(256):
            if b not in bs:
                bs.append(b)
                cs.append(256 + n)
                n += 1
        uni2byte = {chr(c): b for b, c in zip(bs, cs)}

        special = set(self._tok.all_special_ids or [])
        toks = self._guarded(
            lambda: self._tok.convert_ids_to_tokens(
                list(range(min(vocab_size, len(self._tok))))
            )
        )
        out: List[bytes] = []
        for tid, t in enumerate(toks):
            if t is None or tid in special:
                out.append(b"")
                continue
            if t.startswith("<0x") and t.endswith(">") and len(t) == 6:
                try:
                    out.append(bytes([int(t[3:5], 16)]))
                    continue
                except ValueError:
                    pass
            if all(ch in uni2byte for ch in t):
                out.append(bytes(uni2byte[ch] for ch in t))
            else:
                out.append(t.replace("▁", " ").encode("utf-8"))
        out += [b""] * (vocab_size - len(out))
        return out


class IncrementalDetokenizer:
    """Streaming-safe detokenization for one sequence.

    Decoding each step's token ids independently corrupts characters whose
    bytes span token boundaries (routine for byte-level and BPE
    byte-fallback vocabularies). This emits only the newly *stable* text
    of the sequence's whole decode — a trailing run of U+FFFD replacement
    chars is held back until later tokens complete the sequence — and the
    deltas are, one for one, those of re-decoding the whole id history on
    every push. What a push decodes is a WINDOW of recent ids, so a token
    costs the window and not the history (vLLM's prefix / read offsets).

    The window is `ids[_prefix:]`, and `_read` splits it: the text of
    `ids[:_read]` did not end in U+FFFD when `_read` was set (a CLEAN
    cut: every tokenizer here ends in one lossy UTF-8 decode of
    concatenated bytes, and a text that does not end in U+FFFD ends on
    a whole character, so the ids after the cut decode as they do in the
    whole). A cut is taken after every push that ends clean, and
    `_prefix` follows to the cut before it: the window is then the last
    clean chunk plus what has come since, a few ids whatever the history.
    The chunk `ids[_prefix:_read]` is decoded WITH the new ids (`_base`
    is where its text begins in the whole) because `decode` is not a pure
    function of a suffix: SentencePiece drops the leading space of what
    it is handed (the dummy prefix), and HF's clean-up looks at the
    characters on both sides of a joint. What a window cannot see is what
    lies before its first id, so it starts one clean chunk back: the
    chunk's text takes the leading-space rule and stands on the far side
    of every joint the new ids have. `_prefix` does not move onto a chunk
    that decodes to nothing (ids a decode skips: specials, ids outside
    the table), which would hand the rule to the first new id. A sequence
    that never ends clean (a run of invalid bytes) keeps its window
    growing until it does: the held-back run has to be seen whole."""

    def __init__(self, tokenizer: Tokenizer):
        self._tok = tokenizer
        self._ids: List[int] = []
        self._emitted = 0  # characters of the WHOLE text handed out
        self._prefix = 0  # ids[_prefix:] is what a push decodes
        self._read = 0  # a clean cut, _prefix <= _read <= len(_ids)
        self._base = 0  # characters of the whole text before the window

    def push(self, ids: Sequence[int]) -> str:
        self._ids.extend(int(i) for i in ids)
        text, base = self._tok.decode(self._ids[self._prefix:]), self._base
        end = base + len(text)
        stable_end = end
        while stable_end > self._emitted and text[stable_end - 1 - base] == "�":
            stable_end -= 1
        delta = text[max(self._emitted - base, 0):max(stable_end - base, 0)]
        self._emitted = stable_end
        if stable_end == end and not text.endswith("�"):
            # The whole text (`end` characters) ends clean: take the cut,
            # and move the window's start to the cut before it when the
            # chunk between the two decodes to text.
            if self._read > self._prefix:
                chunk = self._tok.decode(self._ids[self._read:])
                if chunk:
                    self._prefix, self._base = self._read, end - len(chunk)
            self._read = len(self._ids)
        return delta

    def flush(self) -> str:
        """Emit whatever is still held back (end of stream)."""
        text = self._tok.decode(self._ids[self._prefix:])
        delta = text[max(self._emitted - self._base, 0):]
        self._emitted = self._base + len(text)
        return delta

    # State carry-over across a PD handoff: the decode peer must continue
    # the prefill peer's byte/char position or the streamed text diverges
    # from a colocated run. The wire form is (ids, emitted); the offsets
    # are derived: an imported history starts as one window (its first
    # two clean pushes decode it whole) and the cuts follow as above.
    def export_state(self) -> "tuple[List[int], int]":
        return list(self._ids), self._emitted

    @classmethod
    def from_state(
        cls, tokenizer: Tokenizer, ids: Sequence[int], emitted: int
    ) -> "IncrementalDetokenizer":
        d = cls(tokenizer)
        d._ids = [int(i) for i in ids]
        d._emitted = int(emitted)
        return d


def create_tokenizer(path: str = "") -> Tokenizer:
    """Factory (reference: tokenizer_factory.cpp:9-33). Empty path selects
    the byte tokenizer (tests/bench). A model dir first tries the NATIVE
    byte-level BPE family (C++ core, tokenizer/native_bpe.py — the
    reference's native-tokenizer analog); models outside that family
    (SentencePiece, exotic normalizers) and hub ids fall back to
    transformers. XLLM_NATIVE_TOKENIZER=0 forces the HF path."""
    import os

    if not path or path == "byte":
        return ByteTokenizer()
    if os.path.isdir(path) and os.environ.get("XLLM_NATIVE_TOKENIZER") != "0":
        from xllm_service_tpu.tokenizer import (
            native_bpe,
            native_sp,
            native_tiktoken,
        )

        tok = native_bpe.try_load(path)
        if tok is not None:
            return tok
        # SentencePiece family (.model protobuf, Unigram + byte fallback)
        # — the reference's sentencepiece_tokenizer.cpp analog.
        sp = native_sp.try_load(path)
        if sp is not None:
            return sp
        # Tiktoken family (*.tiktoken base64 vocab, rank merges) — the
        # reference's tiktoken_tokenizer.cpp analog.
        tk = native_tiktoken.try_load(path)
        if tk is not None:
            return tk
    return HFTokenizer(path)
