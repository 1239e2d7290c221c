"""Grouped matmul: the dropless expert layer's GEMM (rows sorted by expert).

``out[r] = x[r] @ w[g]`` for every row ``r`` of group ``g``. Groups are
consecutive row ranges whose sizes, ``group_sizes``, are multiples of the
row tile ``ROW_TILE``, so no row tile straddles two groups (the expert layer
pads each group to the tile). The grid is ``(N // tn, M // tm)``, row tiles
innermost; group offsets arrive by scalar prefetch as the group of each row
tile and the count of tiles in use. Consecutive tiles of one group map to
the same weight block, so Pallas fetches each group's weights once per
column block; a group with no rows owns no tile and its weights are never
read. The grid is sized for every row of ``x``; steps past the tiles in use
map to the last tile in use, so they move nothing, and compute nothing.
Rows past the tiles in use are left unwritten: the caller reads only the
rows it placed.

The column block ``tn`` is the widest divisor of ``N`` (whole lanes) whose
double-buffered blocks fit half the target's scoped VMEM: at the
expert widths of a 2,048-wide model that is the whole expert matrix, so a
call makes one grid step per row tile.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.plan import HardwareTarget, default_target, pallas_interpret

ROW_TILE = 32  # rows per tile: bf16 sublane-aligned, ~ a decode expert's share


def _gmm_kernel(tile_group_ref, n_tiles_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < n_tiles_ref[0])
    def _compute():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def gmm_geometry(x, w, target: Optional[HardwareTarget]
                 ) -> Tuple[int, int, Tuple[int, int]]:
    """(tm, tn, grid) of one launch: see the module docstring. Shapes and
    dtypes only."""
    target = target if target is not None else default_target()
    M, K = x.shape
    N = w.shape[2]
    if M % ROW_TILE:
        raise ValueError(f"grouped_matmul rows ({M}) must be a multiple of "
                         f"the row tile {ROW_TILE}")
    tm = ROW_TILE
    p_x, p_w = jnp.dtype(x.dtype).itemsize, jnp.dtype(w.dtype).itemsize

    def vmem(c):  # the three double-buffered blocks, bytes (out is x's type)
        return 2 * (tm * K * p_x + K * c * p_w + tm * c * p_x)

    tn = N
    if N % 128 == 0:
        fits = [c for c in range(128, N + 1, 128)
                if N % c == 0 and vmem(c) <= target.vmem_limit_bytes // 2]
        tn = max(fits) if fits else 128
    return tm, tn, (N // tn, M // tm)


def tile_groups(group_sizes: jax.Array, M: int, tm: int = ROW_TILE):
    """(group of each of the ``M // tm`` row tiles, tiles in use as a (1,)
    array). Tiles past those in use take the last one's group."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32) // tm)
    n_tiles = ends[-1]
    t = jnp.arange(M // tm, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                        group_sizes.shape[0] - 1).astype(jnp.int32)
    last = group[jnp.maximum(n_tiles - 1, 0)]
    return jnp.where(t < n_tiles, group, last), n_tiles.reshape(1)


def grouped_matmul(x: jax.Array,  # (M, K), rows sorted by group
                   w: jax.Array,  # (G, K, N)
                   group_sizes: jax.Array,  # (G,) int32, multiples of tm
                   target: Optional[HardwareTarget] = None) -> jax.Array:
    """(M, N) in ``x``'s type, accumulated in float32."""
    M, K = x.shape
    G, _, N = w.shape
    target = target if target is not None else default_target()
    tm, tn, grid = gmm_geometry(x, w, target)
    tile_group, n_tiles = tile_groups(group_sizes, M, tm)

    def row(i, nt):  # steps past the tiles in use repeat the last one
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, i, tg, nt: (row(i, nt), 0)),
                pl.BlockSpec((1, K, tn),
                             lambda n, i, tg, nt: (tg[row(i, nt)], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, i, tg, nt: (row(i, nt), n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=target.vmem_limit_bytes),
        interpret=pallas_interpret(),
        name="grouped_matmul",
    )(tile_group, n_tiles, x, w)


# ---------------------------------------------------------------------------
# Measured HBM traffic, in 32-bit words, from launch geometry (shape-only).
# ---------------------------------------------------------------------------

def _words_per_elem(dtype) -> float:
    return jnp.dtype(dtype).itemsize / 4.0


def grouped_matmul_hbm_words(x, w, target: Optional[HardwareTarget] = None
                             ) -> float:
    """Words one launch moves at most: every row tile in use, as many
    groups with rows as there are groups (or tiles): x tiles once per column
    block, each group's weights once, the output once, plus the int32
    scalar prefetch (a group per tile and the tile count). An upper bound:
    the tiles in use and the groups with rows are known only on the device
    (the expert layer's ``moe_rows`` and ``moe_groups`` counters)."""
    M, K = x.shape
    G, _, N = w.shape
    tm, tn, (nN, nT) = gmm_geometry(x, w, target)
    p_x = _words_per_elem(x.dtype)
    return (nN * M * K * p_x + min(G, nT) * K * N * _words_per_elem(w.dtype)
            + M * N * p_x + nT + 1)


def grouped_matmul_access_plan(x, w, target: Optional[HardwareTarget] = None,
                               tile_group=None, op: str = "grouped_matmul"):
    """The :class:`repro.verify.access.KernelAccessPlan` of one launch.
    ``tile_group`` (a group per row tile, all tiles in use) defaults to the
    tiles spread in order over ``min(G, tiles)`` groups, the layout
    :func:`grouped_matmul_hbm_words` prices."""
    import numpy as np

    from repro.verify.access import (BlockAccess, FlatAccess,
                                     KernelAccessPlan, ScratchAlloc)

    M, K = x.shape
    G, _, N = w.shape
    tm, tn, grid = gmm_geometry(x, w, target)
    nT = grid[1]
    if tile_group is None:
        tile_group = np.arange(nT, dtype=np.int64) * min(G, nT) // nT
    tg = np.asarray(tile_group, dtype=np.int64)
    p_x, p_w = _words_per_elem(x.dtype), _words_per_elem(w.dtype)
    accesses = (
        BlockAccess(name="x", kind="load", block_shape=(tm, K),
                    array_shape=(M, K), word_size=p_x,
                    index_map=lambda n, i: (i, 0)),
        BlockAccess(name="w", kind="load", block_shape=(1, K, tn),
                    array_shape=(G, K, N), word_size=p_w,
                    index_map=lambda n, i: (tg[i], 0, n)),
        BlockAccess(name="out", kind="store", block_shape=(tm, tn),
                    array_shape=(M, N), word_size=p_x,
                    index_map=lambda n, i: (i, n)),
        FlatAccess(name="tile_group", kind="load", words=float(nT),
                   note="int32 scalar prefetch, charged by words_fn"),
        FlatAccess(name="n_tiles", kind="load", words=1.0,
                   note="int32 scalar prefetch, charged by words_fn"),
    )
    scratch = (
        ScratchAlloc("x_pipeline[2]", 2 * tm * K * p_x),
        ScratchAlloc("w_pipeline[2]", 2 * K * tn * p_w),
        ScratchAlloc("out_pipeline[2]", 2 * tm * tn * p_x),
    )
    return KernelAccessPlan(op=op, grid=grid, accesses=accesses,
                            scratch=scratch,
                            note="steps past the tiles in use move nothing")
