# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""TokenLoader: (B, T) next-token batches, produced off the critical path.

Python binding (ctypes — no pybind11 in this image) over the native C++
pipeline in native/dataloader.cpp; compiled on first use with g++ and cached
next to the source under a name keyed by the source's content (a copied
tree keeps no meaningful mtimes, so a stale binary must not be picked up by
date).  Falls back to a NumPy implementation with identical semantics when
no compiler is available; `TokenLoader.backend` says which one runs.

Two modes, both deterministic per seed:
  * corpus mode: `TokenLoader("tokens.bin", ...)` — random crops of a
    memory-mapped uint16 (or `.u32`) token file, targets pre-shifted;
  * synthetic mode: `TokenLoader(None, vocab_size=...)` — uniform random
    tokens, the reference demo workload (example/ddp/train.py:23-24) without
    per-step host tensor construction.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from ..utils.profiling import span

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_SRC = os.path.abspath(os.path.join(_NATIVE_DIR, "dataloader.cpp"))


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.abspath(os.path.join(
        _NATIVE_DIR, f"libtds_dataloader-{digest}.so"))


_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None

# rng-key tag separating the indexed per-sample stream from the per-batch
# stream (both key off (seed, ...)); a constant, never a knob
_IDX_TAG = 0x1D5A


def _load_native():
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                # build beside the target and rename: a concurrent process
                # (test workers) never loads a half-written library
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                     "-pthread", _SRC, "-o", tmp],
                    check=True, capture_output=True, text=True,
                )
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
            lib.tds_loader_create.restype = ctypes.c_void_p
            lib.tds_loader_create.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ]
            lib.tds_loader_next.restype = ctypes.c_int
            lib.tds_loader_next.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.tds_loader_tokens.restype = ctypes.c_longlong
            lib.tds_loader_tokens.argtypes = [ctypes.c_void_p]
            lib.tds_loader_destroy.restype = None
            lib.tds_loader_destroy.argtypes = [ctypes.c_void_p]
            lib.tds_loader_error.restype = ctypes.c_char_p
            _lib = lib
        except Exception as e:  # no compiler / build failure -> fallback
            _build_error = str(e)
        return _lib


def native_available() -> bool:
    return _load_native() is not None


def native_build_error() -> Optional[str]:
    """Why the native loader is unavailable (None when it loaded)."""
    _load_native()
    return _build_error


class TokenLoader:
    """Iterator of (x, y) int32 arrays of shape (batch, seq)."""

    def __init__(self, path: Optional[str], batch: int, seq: int,
                 vocab_size: int = 50304, seed: int = 0,
                 prefetch: int = 4, threads: int = 2,
                 force_numpy: bool = False, indexed: bool = False):
        self.batch, self.seq, self.vocab = batch, seq, vocab_size
        self.seed = seed
        # indexed mode (elastic resume, resilience/elastic.py): sample g
        # of the GLOBAL stream is drawn from rng((seed, _IDX_TAG, g)) —
        # deterministic per sample index regardless of how samples are
        # batched, so a run resumed with a DIFFERENT global batch size
        # continues at an exact sample offset with nothing skipped or
        # repeated.  Numpy path only (the native pipeline's stream is
        # per-batch); seek_samples accepts any offset.
        self.indexed = bool(indexed)
        self.samples_seen = 0
        self._handle = None
        self._lib = (None if force_numpy or indexed
                     else _load_native())
        self.backend = "numpy"

        if self._lib is not None:
            handle = self._lib.tds_loader_create(
                path.encode() if path else None, vocab_size, batch, seq,
                seed, prefetch, threads,
            )
            if handle:
                self._handle = ctypes.c_void_p(handle)
                self.backend = "native"
            else:
                err = self._lib.tds_loader_error().decode()
                if path:  # corpus problems should not be silently eaten
                    raise FileNotFoundError(err or f"cannot load {path}")

        if self._handle is None:  # NumPy fallback, same semantics
            self._rng_counter = 0
            if path:
                width = np.uint32 if path.endswith(".u32") else np.uint16
                self._tokens = np.memmap(path, dtype=width, mode="r")
                if self._tokens.size < seq + 2:
                    raise FileNotFoundError("corpus smaller than one sequence")
            else:
                self._tokens = None

    # -- iteration ---------------------------------------------------------

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        with span("tds.load"):
            return self._next()

    def _next(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._handle is not None:
            x = np.empty((self.batch, self.seq), np.int32)
            y = np.empty((self.batch, self.seq), np.int32)
            rc = self._lib.tds_loader_next(
                self._handle,
                x.ctypes.data_as(ctypes.c_void_p),
                y.ctypes.data_as(ctypes.c_void_p),
            )
            if rc != 0:
                raise RuntimeError("loader stopped")
            self.samples_seen += self.batch
            return x, y
        out = self._numpy_next()
        self.samples_seen += self.batch
        return out

    def _numpy_next(self):
        if self.indexed:
            return self._indexed_next()
        rng = np.random.default_rng((self.seed, self._rng_counter))
        self._rng_counter += 1
        if self._tokens is not None:
            usable = self._tokens.size - self.seq - 1
            starts = rng.integers(0, usable, size=self.batch)
            return self._crops(starts)
        seqs = rng.integers(
            0, self.vocab, size=(self.batch, self.seq + 1), dtype=np.int32
        )
        return seqs[:, :-1], seqs[:, 1:]

    def _indexed_next(self):
        """One batch in indexed mode: samples [samples_seen,
        samples_seen + batch) of the global per-sample stream.

        Cost note: one default_rng construction (SeedSequence hash) per
        sample per batch, ~20-30us each — a permanent host-side cost of
        ~b*25us/step once a run switches to the indexed stream.  A
        counter-based generator (one Philox jumped to the sample offset,
        drawing the batch vectorized) would remove it, but bounded
        integer draws consume a value-dependent number of words
        (rejection sampling), so fixed per-sample counter strides need a
        raw-word + modulo scheme — a distribution change not worth it at
        example scale."""
        base = self.samples_seen
        if self._tokens is not None:
            usable = self._tokens.size - self.seq - 1
            starts = [
                int(np.random.default_rng(
                    (self.seed, _IDX_TAG, base + j)
                ).integers(0, usable))
                for j in range(self.batch)
            ]
            return self._crops(starts)
        seqs = np.stack([
            np.random.default_rng((self.seed, _IDX_TAG, base + j)).integers(
                0, self.vocab, size=self.seq + 1, dtype=np.int32
            )
            for j in range(self.batch)
        ])
        return seqs[:, :-1], seqs[:, 1:]

    def _crops(self, starts):
        x = np.stack([
            self._tokens[s:s + self.seq] for s in starts
        ]).astype(np.int32)
        y = np.stack([
            self._tokens[s + 1:s + self.seq + 1] for s in starts
        ]).astype(np.int32)
        return x, y

    def seek_samples(self, n: int) -> None:
        """Fast-forward the stream to global sample offset `n` (the
        elastic-resume data contract: nothing skipped, nothing repeated).
        Indexed mode accepts any offset directly; the per-batch backends
        (native / plain numpy) require batch alignment — the numpy path
        jumps its counter, the native pipeline replays batches."""
        n = int(n)
        if n < self.samples_seen:
            raise ValueError(
                f"cannot seek backwards (at sample {self.samples_seen}, "
                f"asked for {n}); build a fresh loader"
            )
        if self.indexed:
            self.samples_seen = n
            return
        if (n - self.samples_seen) % self.batch:
            raise ValueError(
                f"seek to sample {n} is not batch-aligned for "
                f"batch={self.batch} (at {self.samples_seen}); use "
                f"TokenLoader(indexed=True) for arbitrary offsets"
            )
        if self._handle is not None:
            while self.samples_seen < n:
                self.next()
            return
        skip = (n - self.samples_seen) // self.batch
        self._rng_counter += skip
        self.samples_seen = n

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    @property
    def n_tokens(self) -> Optional[int]:
        if self._handle is not None:
            return int(self._lib.tds_loader_tokens(self._handle))
        return None if self._tokens is None else int(self._tokens.size)

    def close(self):
        if self._handle is not None:
            self._lib.tds_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
