"""Small shared utilities used across the repro framework.

Pure-JAX helpers only — no framework dependencies. Everything here is
deliberately boring: pytree manipulation, deterministic RNG splitting,
shape/dtype formatting, and simple logging.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import os
import time
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:  # configure once; callers may reconfigure
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(name)s %(levelname)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)

PyTree = Any

# persistent XLA compilation cache when JAX_COMPILATION_CACHE_DIR is unset:
# a fixed directory in the checkout, so reruns of the same code hit it
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads the
    variable itself) and no other directory is set; otherwise the cache
    lives at :data:`COMPILE_CACHE_DIR` inside the checkout.  Returns the
    directory in use.  Call it from ``__main__`` blocks, not from library
    code, so tests that import the entry points leave the cache alone.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    """Total byte footprint across all leaves (respects per-leaf dtype)."""
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        dt = jnp.dtype(x.dtype)
        if dt == jnp.dtype(jnp.int4):
            total += int(np.prod(x.shape)) // 2
        else:
            total += int(np.prod(x.shape)) * dt.itemsize
    return total


def tree_cast(tree: PyTree, dtype) -> PyTree:
    """Cast every floating leaf to `dtype`, leaving integer leaves alone."""
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, tree)


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def tree_flatten_with_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """Flatten a nested-dict pytree into ('a/b/c', leaf) pairs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(_path_str(p) for p in path)
        out.append((name, leaf))
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(sum(leaves))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def round_ste(x: jax.Array) -> jax.Array:
    """Round with a straight-through gradient estimator."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def next_multiple(x: int, m: int) -> int:
    return ceil_div(x, m) * m


def is_power_of_two(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


# ---------------------------------------------------------------------------
# formatting
# ---------------------------------------------------------------------------

def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


class StepTimer:
    """Wall-clock timer with percentile stats — used by the straggler monitor."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self.samples: list[float] = []
        self._t0: float | None = None

    def start(self) -> None:
        self._t0 = self._clock()

    def stop(self) -> float:
        assert self._t0 is not None, "StepTimer.stop() before start()"
        dt = self._clock() - self._t0
        self.samples.append(dt)
        self._t0 = None
        return dt

    def percentile(self, q: float) -> float:
        if not self.samples:
            return 0.0
        return float(np.percentile(np.asarray(self.samples), q))

    def is_straggler(self, dt: float, factor: float = 2.0, min_samples: int = 8) -> bool:
        """A step is a straggler if it exceeds `factor` x median of history."""
        if len(self.samples) < min_samples:
            return False
        return dt > factor * self.percentile(50.0)


def pretty_table(rows: Sequence[Sequence[Any]], header: Sequence[str]) -> str:
    cols = [header] + [[str(c) for c in r] for r in rows]
    widths = [max(len(str(row[i])) for row in cols) for i in range(len(header))]
    lines = []
    lines.append(" | ".join(str(h).ljust(w) for h, w in zip(header, widths)))
    lines.append("-+-".join("-" * w for w in widths))
    for r in rows:
        lines.append(" | ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(lines)
