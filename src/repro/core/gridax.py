"""On-device DSE grid evaluation — the JAX backend of the grid front-end.

The exhaustive search is two separable cost matrices plus a handful of
reductions.  This module runs the integer ones on the default JAX
device: the outer add routed through the ``s3_of``/``b3_of``/``v_of``/
``w_of`` projections, and argmin/argmax for best/worst — ``jax.jit``/
``jax.vmap`` for the general path, and a fused Pallas outer-add+argmin
kernel (``repro.kernels.reduce``) for the cycles-only reduction —
selected per search via ``Study(backend="jax")`` /
``Study(backend="jax-fused")`` or ``$REPRO_DSE_BACKEND``.

Bit-identity contract (pinned by ``tests/test_gridax*.py`` against the
numpy engine and the scalar ``search_reference``, and on a TPU by
``chip_smoke.py``):

  * **int64 cycles.**  The jits run under ``jax.enable_x64(True)``:
    outside it jnp silently defaults to int32 and cycle grids past 2**31
    (Table VIII training) would truncate.  x64 participates in the jit
    cache key, so these jits never collide with the repo's f32 kernel
    wrappers.  The fused kernel needs no x64: it carries each int64 as
    two int32 words (see ``repro.kernels.reduce``).
  * **First-occurrence ties.**  ``jnp.argmin``/``argmax`` return the
    first occurrence, matching the legacy strict-inequality
    (size-outer, bandwidth-inner) walk; the fused kernel keeps the same
    contract.
  * **No float on the device, by design.**  A TPU has no float64 units,
    so float64 arithmetic there is not numpy's IEEE arithmetic.  Every
    float step — the within-frac frontier (``costs <= best * 1.15``),
    energy/EDP scoring and its argmin, the Pareto mask — therefore runs
    on the host, in the code the numpy backend runs.  The device only
    adds, compares and selects integers, where exactness needs no
    argument.

Grids come back as device arrays (``jax.Array``); the caller copies
what it keeps to the host.  The retained ``DSEGrid``/``DSEResult``
machinery downstream is shared with the numpy backend, which is what
keeps every accessor (``points``, ``economic_min_*``, ``pareto`` …)
identical by construction.

Importing this module is how a process enters the device path, so it
also places JAX's persistent compilation cache (``use_compile_cache``)
and records JAX's compiles as ``repro.jax.compile`` spans
(``tracing.watch_jax_compiles``).
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.reduce import grid_minmax_pallas
from . import tracing

# <checkout>/.jax_cache: a fixed path (the cache is keyed on it), listed
# in .gitignore
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across processes.  When
    ``$JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing; otherwise the cache lives in ``COMPILE_CACHE_DIR``.
    The one place in the program that sets a cache directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(COMPILE_CACHE_DIR))


use_compile_cache()
tracing.watch_jax_compiles()


def _x64(fn):
    """Run ``fn`` (tracing and execution) under the x64 context so int64
    grids stay int64 — the context is thread-local and part of the jit
    cache key."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)
    return wrapper


# ---------------------------------------------------------------------------
# jit'd reductions
# ---------------------------------------------------------------------------

def _outer_add_impl(conv, simd, s3_of, b3_of, v_of, w_of):
    return conv[s3_of][:, b3_of] + simd[v_of][:, w_of]


def _reduce_cycles_impl(conv, simd, s3_of, b3_of, v_of, w_of):
    costs = _outer_add_impl(conv, simd, s3_of, b3_of, v_of, w_of)
    flat = costs.ravel()
    return costs, jnp.argmin(flat), jnp.argmax(flat)


_outer_add_jit = _x64(jax.jit(_outer_add_impl))
_reduce_cycles_one = _x64(jax.jit(_reduce_cycles_impl))
# vmap over stacked per-network matrices: the projections are shared by
# every network of one search, so a multi-net cycles sweep is a single
# batched dispatch
_reduce_cycles_vmap = _x64(jax.jit(jax.vmap(
    _reduce_cycles_impl, in_axes=(0, 0, None, None, None, None))))


# ---------------------------------------------------------------------------
# Public entry points (numpy in; grids out as device arrays)
# ---------------------------------------------------------------------------

def outer_add(conv: np.ndarray, simd: np.ndarray,
              s3_of: np.ndarray, b3_of: np.ndarray,
              v_of: np.ndarray, w_of: np.ndarray) -> jax.Array:
    """The device outer-add composition — int64-exact equivalent of
    ``conv[np.ix_(s3_of, b3_of)] + simd[np.ix_(v_of, w_of)]``."""
    return _outer_add_jit(conv, simd, s3_of, b3_of, v_of, w_of)


def fused_minmax(conv: np.ndarray, simd: np.ndarray,
                 s3_of: np.ndarray, b3_of: np.ndarray,
                 v_of: np.ndarray, w_of: np.ndarray,
                 interpret: Optional[bool] = None) -> Tuple[int, int]:
    """(argmin, argmax) flat indices of the virtual cost grid via the
    fused Pallas kernel — the grid itself is never materialized: columns
    are pre-gathered into two small operand panels, rows are gathered
    in the kernel through the SMEM projections."""
    out = grid_minmax_pallas(conv[:, b3_of], simd[:, w_of], s3_of, v_of,
                             interpret=interpret)
    return int(out[1]), int(out[3])


@_x64
def reduce_cycles_many(convs: Sequence[np.ndarray],
                       simds: Sequence[np.ndarray],
                       s3_of: np.ndarray, b3_of: np.ndarray,
                       v_of: np.ndarray, w_of: np.ndarray, *,
                       fused: bool = False,
                       interpret: Optional[bool] = None
                       ) -> List[Tuple[jax.Array, int, int]]:
    """The cycles-objective reduction for N networks sharing one
    candidate space: per network ``(costs, best_idx, worst_idx)`` with
    ``costs`` on the device.  Multiple networks run as one vmapped
    dispatch; ``fused`` routes best/worst through the Pallas kernel
    instead of XLA argmin."""
    if fused:
        return [(outer_add(conv, simd, s3_of, b3_of, v_of, w_of),
                 *fused_minmax(conv, simd, s3_of, b3_of, v_of, w_of,
                               interpret=interpret))
                for conv, simd in zip(convs, simds)]
    if len(convs) == 1:
        costs, bi, wi = _reduce_cycles_one(convs[0], simds[0],
                                           s3_of, b3_of, v_of, w_of)
        return [(costs, int(bi), int(wi))]
    costs, bi, wi = _reduce_cycles_vmap(np.stack(convs), np.stack(simds),
                                        s3_of, b3_of, v_of, w_of)
    bi, wi = np.asarray(bi), np.asarray(wi)
    return [(costs[n], int(bi[n]), int(wi[n])) for n in range(len(convs))]
