"""Shape bucketing + compile-cache accounting for the detection plane.

jit/Pallas executables are keyed by concrete shapes. A streaming detector
sees a different window length every sweep, so naive calls would recompile
per sweep — recompilation (hundreds of ms) dwarfs the kernel itself (sub-ms).
The fix the stream scorer already used, promoted here to shared
infrastructure: pad the row count to a power-of-two bucket and pass the true
row count as a *traced* ``nvalid`` argument, so one executable serves every
window size in the bucket.

`ShapeBucketCache` additionally keeps hit/miss counts per (bucket, D, K)
signature — a miss means a fresh XLA compile on the sweep that saw it — and
those counts feed the ``eacgm_detect_compile_*`` self-metrics.

`enable_persistent_cache` opts into JAX's on-disk compilation cache so the
first sweep of a *process* doesn't pay the compile either.
"""
from __future__ import annotations

import os
import pathlib
import threading
from typing import Dict, Tuple

import numpy as np

MIN_BUCKET = 256


def bucket_rows(n: int, min_bucket: int = MIN_BUCKET) -> int:
    """Next power-of-two row count >= max(n, min_bucket)."""
    b = max(int(min_bucket), 1)
    n = int(n)
    while b < n:
        b <<= 1
    return b


def pad_to_bucket(X: np.ndarray, min_bucket: int = MIN_BUCKET
                  ) -> Tuple[np.ndarray, int]:
    """Zero-pad X's rows to its bucket; returns (padded, true row count).

    Padding rows are masked out inside the kernels via ``nvalid``, so they
    contribute nothing — they only stabilise the compiled shape."""
    n = int(X.shape[0])
    b = bucket_rows(n, min_bucket)
    if b == n:
        return X, n
    pad = np.zeros((b - n,) + X.shape[1:], dtype=X.dtype)
    return np.concatenate([X, pad], axis=0), n


class ShapeBucketCache:
    """Tracks which compiled-shape signatures the detection plane has paid
    for. Record one signature per kernel call site; the first sighting is a
    miss (an XLA compile happened on that sweep), repeats are hits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seen: Dict[Tuple, int] = {}
        self._hits = 0
        self._misses = 0

    def record(self, *signature) -> bool:
        """Record a call with this shape signature; True if already compiled."""
        with self._lock:
            if signature in self._seen:
                self._seen[signature] += 1
                self._hits += 1
                return True
            self._seen[signature] = 1
            self._misses += 1
            return False

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self._hits, "misses": self._misses,
                    "shapes": len(self._seen)}


# Process-wide instance: every detector shares one accounting surface, the
# same way every jit call shares one XLA executable cache.
SHAPE_CACHE = ShapeBucketCache()

# Fixed and in the checkout: a cache directory that moves between runs (a
# temporary, pid- or time-derived path) is never found again.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's on-disk compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache (JAX reads it
    itself, so no directory is set here); otherwise it is the checkout's
    ``.jax_cache``. Launchers call this once at start-up; tests never do.
    With it, shape-bucket misses and the step program cost a cache *read*
    instead of a compile from the second process onwards."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the default floor (1 s) would skip exactly the small GMM kernels
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
