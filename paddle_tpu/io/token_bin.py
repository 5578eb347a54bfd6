"""Native token-bin reader: ctypes binding over native/libfastloader.so
(the C++ mmap + prefetch-ring data runtime; see native/fastloader.cpp for
the reference mapping). Yields (input_ids, labels) int32 numpy batches.
"""
from __future__ import annotations

import ctypes
import os

import numpy as np

from paddle_tpu.io.dataset import IterableDataset

_LIB = None


def _load_lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    from paddle_tpu.utils.native import build_native
    lib = ctypes.CDLL(build_native("libfastloader.so"))
    lib.fl_open.restype = ctypes.c_void_p
    lib.fl_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                            ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
    lib.fl_next.restype = ctypes.c_int
    lib.fl_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
    lib.fl_num_tokens.restype = ctypes.c_uint64
    lib.fl_num_tokens.argtypes = [ctypes.c_void_p]
    lib.fl_close.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


class TokenBinDataset(IterableDataset):
    """Streams random (seq+1)-token windows from a binary token file.

    File format: flat little-endian uint16 (default) or uint32 token ids —
    the standard nanoGPT/megatron .bin layout.
    """

    def __init__(self, path: str, batch_size: int, seq_len: int, seed: int = 0,
                 token_width: int = 2, num_workers: int = 2, prefetch: int = 8,
                 num_batches: int | None = None,
                 shard: tuple[int, int] | None = None):
        """``shard=(rank, world)`` de-correlates the random-window stream
        across hosts (each host draws from a distinct seeded stream — the
        standard dp recipe for window-sampling loaders). ``shard=None``
        auto-detects from the launch env contract (PROCESS_ID /
        NUM_PROCESSES) or an ALREADY-INITIALIZED jax.distributed runtime;
        it never initializes the backend itself (constructing a dataset
        before ``launch.initialize_cluster()`` must stay side-effect-free),
        falling back to (0, 1)."""
        if shard is None:
            rank = int(os.environ.get("PROCESS_ID", "-1"))
            world = int(os.environ.get("NUM_PROCESSES", "-1"))
            if world > 0 and 0 <= rank < world:
                shard = (rank, world)
            else:
                try:
                    from jax._src import distributed as _jd
                    if _jd.global_state.client is not None:
                        import jax
                        shard = (jax.process_index(), jax.process_count())
                    else:
                        shard = (0, 1)
                except Exception:
                    shard = (0, 1)
        rank, world = shard
        if not (0 <= rank < world):
            raise ValueError(f"bad shard {shard}")
        self.shard = (rank, world)
        self.path = os.fspath(path)
        self.batch_size = batch_size
        self.seq_len = seq_len
        seed = seed * world + rank  # distinct stream per host
        self.seed = seed
        self.token_width = token_width
        self.num_workers = num_workers
        self.prefetch = prefetch
        self.num_batches = num_batches
        self._lib = _load_lib()
        self._handle = None

    def _open(self):
        h = self._lib.fl_open(self.path.encode(), self.token_width,
                              self.batch_size, self.seq_len, self.seed,
                              self.num_workers, self.prefetch)
        if not h:
            raise OSError(f"fastloader: cannot open {self.path}")
        return h

    @property
    def num_tokens(self) -> int:
        h = self._handle or self._open()
        n = int(self._lib.fl_num_tokens(h))
        if self._handle is None:
            self._lib.fl_close(h)
        return n

    def __iter__(self):
        h = self._open()
        window = self.seq_len + 1
        buf = np.empty((self.batch_size, window), dtype=np.int32)
        try:
            produced = 0
            while self.num_batches is None or produced < self.num_batches:
                rc = self._lib.fl_next(h, buf.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int32)))
                if rc != 0:
                    break
                yield buf[:, :-1].copy(), buf[:, 1:].copy()
                produced += 1
        finally:
            self._lib.fl_close(h)
