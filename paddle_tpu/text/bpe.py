"""Byte-level BPE tokenizer (capability ref: PaddleNLP FastTokenizer /
GPT-2-style BPE).

Training (offline) is Python; the per-text encode hot loop runs in
``native/libfastbpe.so`` via ctypes (calls release the GIL, so a thread
pool scales batch encoding across cores). The library is built from
source on first use; ``use_native=False`` selects the pure-Python encoder
of the same algorithm (no compiler needed, and the tests' reference).
"""
from __future__ import annotations

import ctypes
import json
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _load_native():
    from paddle_tpu.utils.native import build_native
    lib = ctypes.CDLL(build_native("libfastbpe.so"))
    lib.bpe_new.restype = ctypes.c_void_p
    lib.bpe_new.argtypes = [ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                            ctypes.POINTER(ctypes.c_int32)]
    lib.bpe_free.argtypes = [ctypes.c_void_p]
    lib.bpe_encode.restype = ctypes.c_int64
    lib.bpe_encode.argtypes = [ctypes.c_void_p,
                               ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
    return lib


_LIB = None


class BPETokenizer:
    """vocab: id -> bytes; merges: ordered list of (left_id, right_id)."""

    def __init__(self, merges, special_tokens=None, use_native=True):
        self.merges = [tuple(m) for m in merges]
        # ids 0..255 are the raw bytes; merged tokens follow in rank order
        self.vocab = {i: bytes([i]) for i in range(256)}
        self._ranks = {}
        for rank, (a, b) in enumerate(self.merges):
            new_id = 256 + rank
            self.vocab[new_id] = self.vocab[a] + self.vocab[b]
            self._ranks[(a, b)] = (rank, new_id)
        self.special_tokens = dict(special_tokens or {})  # str -> id
        for tok, tid in self.special_tokens.items():
            self.vocab[tid] = tok.encode("utf-8")
        self._handle = None
        if use_native:
            global _LIB
            if _LIB is None:
                _LIB = _load_native()
            flat = np.asarray([[a, b, 256 + r] for r, (a, b)
                               in enumerate(self.merges)],
                              np.int32).reshape(-1)
            byte_ids = np.arange(256, dtype=np.int32)
            self._merges_buf = flat  # keep alive
            self._bytes_buf = byte_ids
            self._handle = _LIB.bpe_new(
                flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                len(self.merges),
                byte_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))

    def __del__(self):
        if getattr(self, "_handle", None) and _LIB is not None:
            _LIB.bpe_free(self._handle)
            self._handle = None

    @property
    def vocab_size(self):
        return 256 + len(self.merges) + len(self.special_tokens)

    # -- training ------------------------------------------------------------
    @classmethod
    def train(cls, texts, vocab_size=1024, special_tokens=("<pad>", "<eos>"),
              use_native=True):
        """Classic BPE training: repeatedly merge the most frequent pair.
        Words are whitespace-chunked (spaces kept with the following word,
        GPT-2 style) so merges never cross word boundaries."""
        words = Counter()
        for t in texts:
            for i, w in enumerate(t.split(" ")):
                words[(" " if i else "") + w] += 1
        seqs = {w: list(w.encode("utf-8")) for w in words}
        merges = []
        n_special = len(special_tokens)
        while 256 + len(merges) + n_special < vocab_size:
            pairs = Counter()
            for w, cnt in words.items():
                s = seqs[w]
                for i in range(len(s) - 1):
                    pairs[(s[i], s[i + 1])] += cnt
            if not pairs:
                break
            (a, b), freq = pairs.most_common(1)[0]
            if freq < 2:
                break
            new_id = 256 + len(merges)
            merges.append((a, b))
            for w in seqs:
                s = seqs[w]
                out, i = [], 0
                while i < len(s):
                    if i + 1 < len(s) and s[i] == a and s[i + 1] == b:
                        out.append(new_id)
                        i += 2
                    else:
                        out.append(s[i])
                        i += 1
                seqs[w] = out
        specials = {t: 256 + len(merges) + i
                    for i, t in enumerate(special_tokens)}
        return cls(merges, specials, use_native=use_native)

    # -- encoding ------------------------------------------------------------
    @staticmethod
    def _chunks(text):
        """Split like training (spaces bind to the following word): merges
        never cross these boundaries, so per-chunk encoding is byte-identical
        to whole-text encoding while keeping the greedy loop O(word²)."""
        for i, w in enumerate(text.split(" ")):
            c = (" " if i else "") + w
            if c:
                yield c

    def _encode_seq_py(self, chunk):
        ids = list(chunk.encode("utf-8"))
        while len(ids) >= 2:
            best = None
            for i in range(len(ids) - 1):
                r = self._ranks.get((ids[i], ids[i + 1]))
                if r is not None and (best is None or r[0] < best[0]):
                    best = (r[0], i, r[1])
            if best is None:
                break
            _, i, new_id = best
            ids[i:i + 2] = [new_id]
        return ids

    def _encode_seq_native(self, chunk):
        raw = chunk.encode("utf-8")
        buf_len = max(len(raw), 1)
        buf = np.empty(buf_len, np.int32)
        src = np.frombuffer(raw, np.uint8)
        n = _LIB.bpe_encode(
            self._handle,
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(raw),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), buf_len)
        if n < 0:  # can't happen: output never exceeds input bytes
            raise RuntimeError("bpe_encode: output buffer too small")
        return buf[:n].tolist()

    def encode(self, text):
        enc = (self._encode_seq_native if self._handle is not None
               else self._encode_seq_py)
        out = []
        for chunk in self._chunks(text):
            out.extend(enc(chunk))
        return out

    def encode_batch(self, texts, num_threads=4):
        """Parallel batch encode — the native calls drop the GIL."""
        if self._handle is None or num_threads <= 1:
            return [self.encode(t) for t in texts]
        with ThreadPoolExecutor(num_threads) as ex:
            return list(ex.map(self.encode, texts))

    def decode(self, ids):
        return b"".join(self.vocab[int(i)] for i in ids).decode(
            "utf-8", errors="replace")

    # -- persistence ---------------------------------------------------------
    def save(self, path):
        with open(path, "w") as f:
            json.dump({"merges": self.merges,
                       "special_tokens": self.special_tokens}, f)

    @classmethod
    def load(cls, path, use_native=True):
        with open(path) as f:
            d = json.load(f)
        return cls(d["merges"], d["special_tokens"], use_native=use_native)
