"""Jit'd public wrappers around the Pallas kernels.

On CPU hosts (this container) the kernels execute under
``interpret=True`` — the kernel body runs as regular JAX ops so the
BlockSpec/when logic is validated end-to-end; on TPU they compile to
Mosaic. Call sites never need to care.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.distance import partial_distance_update as _pallas_update
from repro.kernels.distance import tile_skip_map
from repro.kernels.distance_int8 import (
    int8_partial_distance_update as _pallas_update_int8,
)
from repro.kernels.topk_update import running_topk_update as _pallas_topk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def partial_distance_update(
    x: jnp.ndarray,
    xn2: jnp.ndarray,
    q: jnp.ndarray,
    qn2: jnp.ndarray,
    acc: jnp.ndarray,
    tau: jnp.ndarray,
    *,
    prune: bool = True,
    metric: str = "l2",
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """acc' = acc + partial_distance_block, pruned against τ.

    Returns (acc' [M,N] f32, tile_skip_map [m_tiles, n_tiles] int32).
    ``use_pallas=False`` routes to the pure-jnp oracle (fast XLA path used
    by CPU-measured benchmarks; the skip map is then computed post-hoc).
    """
    if interpret is None:
        interpret = not _on_tpu()
    if use_pallas:
        return _pallas_update(
            x, xn2, q, qn2, acc, tau,
            prune=prune, metric=metric,
            tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
            interpret=interpret,
        )
    out = ref.partial_distance_update_ref(
        x, xn2, q, qn2, acc, tau, prune=prune, metric=metric
    )
    skip = tile_skip_map(acc, tile_m, tile_n)
    return out, skip


def int8_partial_distance_update(
    x: jnp.ndarray,
    xn2: jnp.ndarray,
    q: jnp.ndarray,
    qn2: jnp.ndarray,
    scale2: jnp.ndarray,
    acc: jnp.ndarray,
    tau: jnp.ndarray,
    *,
    prune: bool = True,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized stage-1 scoring: acc' = acc + s²·‖Q−P‖²_b, pruned vs τ.

    ``x``/``q`` are int8 codes on a shared per-dimension-block grid;
    ``xn2``/``qn2`` carry the pre-scaled s²·Σcode² norms (f32). The MXU
    contraction accumulates in int32. L2 only. Returns
    (acc' [M,N] f32, tile_skip_map [m_tiles, n_tiles] int32).
    """
    if interpret is None:
        interpret = not _on_tpu()
    if use_pallas:
        return _pallas_update_int8(
            x, xn2, q, qn2, scale2, acc, tau,
            prune=prune,
            tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
            interpret=interpret,
        )
    out = ref.int8_partial_distance_update_ref(
        x, xn2, q, qn2, scale2, acc, tau, prune=prune
    )
    skip = tile_skip_map(acc, tile_m, tile_n)
    return out, skip


def running_topk_update(
    scores: jnp.ndarray,      # [M, C] f32, +inf = invalid
    ids: jnp.ndarray,         # [M, C] i32
    run_s: jnp.ndarray,       # [M, K] f32 ascending
    run_i: jnp.ndarray,       # [M, K] i32
    *,
    k: int,
    tile_m: int = 8,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge a candidate chunk into the per-query running top-K.

    Routes to the fused VMEM-resident Pallas kernel (interpret-mode off
    TPU) or the concat+sort jnp oracle with ``use_pallas=False``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if use_pallas:
        return _pallas_topk(
            scores, ids, run_s, run_i, k=k, tile_m=tile_m, interpret=interpret
        )
    return ref.running_topk_ref(scores, ids, run_s, run_i, k=k)


def masked_topk(scores: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Ascending top-k of finite entries (oracle-backed; see ref)."""
    return ref.masked_topk_ref(scores, ids, k)
