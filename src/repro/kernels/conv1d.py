"""Causal depthwise conv1d Pallas kernel (the mamba/xLSTM short convolution).

7NL view: N=B, c_I=c_O=D (depthwise), h=sequence, w_F=K, h_F=1. The blocking
LP degenerates to choosing (b_B, b_D) tiles with the full (short) K window
VMEM-resident; the sequence axis streams whole per tile (K <= 8 in all
assigned archs, L*b_D*2B <= VMEM for every cell incl. 32k prefill at b_D=128).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_model import round_up
from repro.plan import HardwareTarget, default_target, pallas_interpret


def _conv1d_kernel(x_ref, w_ref, o_ref, *, K: int):
    x = x_ref[...].astype(jnp.float32)  # (bB, L, bD)
    w = w_ref[...].astype(jnp.float32)  # (K, bD)
    L = x.shape[1]
    acc = x * w[K - 1][None, None, :]  # tap k = K-1 aligns with current step
    for k in range(K - 1):
        shift = K - 1 - k
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :L, :]
        acc = acc + shifted * w[k][None, None, :]
    o_ref[...] = acc.astype(o_ref.dtype)


def conv1d_causal(
    x: jax.Array,  # (B, L, D)
    w: jax.Array,  # (K, D)
    tiles: Tuple[int, int] | None = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """``target`` (default: the attached device's) sets the channel-tile
    lane width; the degenerate LP solution is the closed form in the module
    docstring."""
    target = target if target is not None else default_target()
    K = w.shape[0]
    bB, bD, Bp, Dp, grid = _conv1d_geometry(x, w, tiles, target)
    B, L, D = x.shape
    if (Bp, Dp) != (B, D):
        x = jnp.pad(x, ((0, Bp - B), (0, 0), (0, Dp - D)))
        w = jnp.pad(w, ((0, 0), (0, Dp - D)))
    out = pl.pallas_call(
        functools.partial(_conv1d_kernel, K=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, L, bD), lambda i, j: (i, 0, j)),
            pl.BlockSpec((K, bD), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bB, L, bD), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, L, Dp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=target.vmem_limit_bytes),
        interpret=pallas_interpret(),
    )(x, w)
    return out[:B, :, :D]


def _conv1d_geometry(x, w, tiles: Tuple[int, int] | None,
                     target: Optional[HardwareTarget]):
    """(bB, bD, Bp, Dp, grid) — the launch geometry :func:`conv1d_causal`
    lowers, shared with the words counter and the access plan."""
    B, L, D = x.shape
    # a lane-wide channel tile and a sublane-high batch tile; a target that
    # asks for no alignment (the CPU) gets the v5e's (8, 128)
    align = target.align if target else (1, 1)
    sublane, lane = (a if a > 1 else v5e for a, v5e in zip(align, (8, 128)))
    bB, bD = tiles or (max(1, min(B, sublane)), max(1, min(D, lane)))
    Bp, Dp = round_up(B, bB), round_up(D, bD)
    return bB, bD, Bp, Dp, (Bp // bB, Dp // bD)


def conv1d_hbm_words(
    x,  # array or ShapeDtypeStruct, (B, L, D)
    w,  # array or ShapeDtypeStruct, (K, D)
    tiles: Tuple[int, int] | None = None,
    target: Optional[HardwareTarget] = None,
) -> float:
    """Measured HBM words (32-bit) one ``conv1d_causal`` dispatch moves: one
    padded input block in and one output block out per (i, j) grid step, plus
    the (K, bD) filter block — fetched once per step when the channel grid
    has > 1 column (its index map (0, j) changes every step), but only once
    in total when nD == 1 (the index map is then constant and Pallas elides
    the re-fetch). Shapes/dtypes only (``jax.ShapeDtypeStruct`` works)."""
    L = x.shape[1]
    K = w.shape[0]
    bB, bD, Bp, Dp, grid = _conv1d_geometry(x, w, tiles, target)
    nB, nD = grid
    p_x = jnp.dtype(x.dtype).itemsize / 4.0
    p_w = jnp.dtype(w.dtype).itemsize / 4.0
    w_fetches = nB * nD if nD > 1 else 1
    return (nB * nD * bB * L * bD * p_x  # input blocks (out dtype = x dtype)
            + w_fetches * K * bD * p_w  # filter blocks
            + nB * nD * bB * L * bD * p_x)  # output stores


def conv1d_access_plan(
    x,  # array or ShapeDtypeStruct, (B, L, D)
    w,  # array or ShapeDtypeStruct, (K, D)
    tiles: Tuple[int, int] | None = None,
    target: Optional[HardwareTarget] = None,
    op: str = "conv1d_causal",
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one
    ``conv1d_causal`` launch (pure BlockSpec pipeline, no manual DMA)."""
    from repro.verify.access import (BlockAccess, KernelAccessPlan,
                                     ScratchAlloc)

    L = x.shape[1]
    K = w.shape[0]
    bB, bD, Bp, Dp, grid = _conv1d_geometry(x, w, tiles, target)
    p_x = jnp.dtype(x.dtype).itemsize / 4.0
    p_w = jnp.dtype(w.dtype).itemsize / 4.0
    accesses = (
        BlockAccess(name="x", kind="load", block_shape=(bB, L, bD),
                    array_shape=(Bp, L, Dp), word_size=p_x,
                    index_map=lambda i, j: (i, 0, j)),
        BlockAccess(name="w", kind="load", block_shape=(K, bD),
                    array_shape=(K, Dp), word_size=p_w,
                    index_map=lambda i, j: (0, j)),
        BlockAccess(name="out", kind="store", block_shape=(bB, L, bD),
                    array_shape=(Bp, L, Dp), word_size=p_x,
                    index_map=lambda i, j: (i, 0, j)),
    )
    scratch = (
        ScratchAlloc("x_pipeline[2]", 2 * bB * L * bD * p_x),
        ScratchAlloc("w_pipeline[2]", 2 * K * bD * p_w),
        ScratchAlloc("out_pipeline[2]", 2 * bB * L * bD * p_x),
    )
    return KernelAccessPlan(op=op, grid=grid, accesses=accesses,
                            scratch=scratch)
