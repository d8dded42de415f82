"""Native data-loader bindings (csrc/ptio.cpp via ctypes).

TokenDataset/TokenDataLoader: the pretraining input pipeline — mmap token
file, C++ threaded prefetch, fixed (B, S) int32 blocks (inputs + next-token
labels). ``libptio.so`` is built on demand (utils/native_build.py); when it
cannot be built a numpy reader with the same sampling contract runs instead,
with a warning that says so — ``TokenDataLoader.native`` reports which ran.
Ref: paddle/fluid/framework/data_feed.cc + fluid/dataloader worker stack.
"""
from __future__ import annotations

import ctypes
import threading
from typing import Optional, Tuple

import numpy as np

_LIB = None
_LIB_LOCK = threading.Lock()


def _build_lib() -> Optional[str]:
    from ..utils.native_build import ensure_lib

    return ensure_lib("ptio")


def get_lib():
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        path = _build_lib()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.ptio_create_reader.restype = ctypes.c_void_p
        lib.ptio_create_reader.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_int]
        lib.ptio_next_batch.restype = ctypes.c_int
        lib.ptio_next_batch.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_int32)]
        lib.ptio_samples_per_shard.restype = ctypes.c_long
        lib.ptio_samples_per_shard.argtypes = [ctypes.c_void_p]
        lib.ptio_destroy_reader.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def write_token_file(tokens: np.ndarray, path: str, dtype=np.int32) -> str:
    """Serialize a 1-D token stream to the binary format the reader mmaps."""
    arr = np.ascontiguousarray(tokens, dtype=dtype)
    with open(path, "wb") as f:
        f.write(arr.tobytes())
    return path


class TokenDataLoader:
    """Pretraining loader: yields (input_ids (B,S) int32, labels (B,S) int64).

    Uses the C++ prefetch core when it builds, else the numpy reader
    (warned once per loader; see :attr:`native`).
    shard_id/num_shards give DistributedBatchSampler-style dataset sharding.
    """

    def __init__(self, path: str, seq_len: int, batch_size: int, dtype_size: int = 4,
                 num_threads: int = 2, capacity: int = 8, seed: int = 0,
                 shard_id: int = 0, num_shards: int = 1):
        self.path = path
        self.seq_len = seq_len
        self.batch_size = batch_size
        self.dtype_size = dtype_size
        self._handle = None
        self._lib = get_lib()
        self._seed = seed
        self._shard = (shard_id, num_shards)
        if self._lib is not None:
            self._handle = self._lib.ptio_create_reader(
                path.encode(), dtype_size, seq_len, batch_size, num_threads,
                capacity, seed, shard_id, num_shards)
            if not self._handle:
                self._lib = None
        if self._lib is None:
            import warnings

            from ..utils import native_build

            warnings.warn(
                "TokenDataLoader: the native reader (csrc/ptio.cpp) is "
                "unavailable — running the numpy reader, without threaded "
                f"prefetch. Last build error: {native_build.LAST_BUILD_ERROR}",
                RuntimeWarning)
            dt = {2: np.uint16, 4: np.int32, 8: np.int64}[dtype_size]
            self._tokens = np.fromfile(path, dtype=dt)
            self._rng = np.random.RandomState(seed)

    @property
    def native(self) -> bool:
        return self._handle is not None

    def samples_per_shard(self) -> int:
        if self._handle:
            return int(self._lib.ptio_samples_per_shard(self._handle))
        stride = self.seq_len + 1
        return (len(self._tokens) // stride) // self._shard[1]

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        stride = self.seq_len + 1
        buf = np.empty((self.batch_size, stride), np.int32)
        if self._handle:
            ok = self._lib.ptio_next_batch(
                self._handle, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            if not ok:
                raise StopIteration
        else:
            n = self.samples_per_shard()
            shard_id, _ = self._shard
            for i in range(self.batch_size):
                s = shard_id * n + self._rng.randint(n)
                buf[i] = self._tokens[s * stride:(s + 1) * stride].astype(np.int32)
        return buf[:, :-1].copy(), buf[:, 1:].astype(np.int64)

    def __iter__(self):
        while True:
            yield self.next()

    def close(self):
        if self._handle:
            self._lib.ptio_destroy_reader(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
