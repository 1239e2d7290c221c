"""LP-tiled Pallas matmul.

Block shapes (bm, bn, bk) come from the paper's blocking LP applied to the
degenerate 7NL CNN (w_F = h_F = w_O = h_O = 1): the same machinery that tiles
convolutions tiles every GEMM in the LM stack. Inputs stream HBM->VMEM in
bf16 (p_I = p_F = 0.5 words); the accumulator tile is f32 (p_O = 1 word) and
stays VMEM-resident across the k reduction — exactly the paper's §5
scratchpad/accumulator discipline.

The A/B streams are double-buffered across the k reduction grid axis (the
same pattern as kernels/conv2d.py): both operands stay in ANY/HBM memory and
the kernel DMAs each (bm, bk)/(bk, bn) block into a two-slot VMEM scratch,
starting step k+1's copies before computing step k's GEMM — this is the
double-buffering the LP's halved capacity (§5) models.

``matmul_hbm_words`` reports the measured HBM words one dispatch moves from
the same launch geometry.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_model import Precision, round_up
from repro.plan import (ExecutionPlan, HardwareTarget, MatmulSpec,
                        pallas_interpret, resolve_kernel_plan,
                        warn_legacy_kernel_kwargs)


def _matmul_spec(m: int, n: int, k: int, in_bits: int) -> MatmulSpec:
    p_in = in_bits / 32.0
    return MatmulSpec(m=m, n=n, k=k, prec=Precision(p_in, p_in, 1.0))


def _matmul_kernel(a_hbm, b_hbm, o_ref, a_vmem, b_vmem, acc_ref, sems, *,
                   nk: int, bm: int, bn: int, bk: int):
    """Grid = (nm, nn, nk); k innermost so the f32 accumulator tile stays
    resident across the reduction (paper §5 loop-order discipline)."""
    i, j, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def stream(slot, k_idx):
        return (
            pltpu.make_async_copy(
                a_hbm.at[pl.ds(i * bm, bm), pl.ds(k_idx * bk, bk)],
                a_vmem.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(
                b_hbm.at[pl.ds(k_idx * bk, bk), pl.ds(j * bn, bn)],
                b_vmem.at[slot], sems.at[slot, 1]),
        )

    @pl.when(ki == 0)
    def _warmup():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in stream(0, 0):
            cp.start()

    slot = ki % 2

    @pl.when(ki + 1 < nk)
    def _prefetch():  # overlap the next k step's DMA with this step's GEMM
        for cp in stream(1 - slot, ki + 1):
            cp.start()

    for cp in stream(slot, ki):
        cp.wait()

    acc_ref[...] += jnp.dot(
        a_vmem[slot], b_vmem[slot], preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    a: jax.Array,  # (m, k)
    b: jax.Array,  # (k, n)
    out_dtype=jnp.float32,
    ctx=None,  # ExecutionContext (duck-typed: .target/.autotune)
    tiles: Tuple[int, int, int] | None = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """C[m,n] = A @ B with LP-chosen VMEM tiling.

    Execution policy rides ``ctx``. Tiles come from (in priority order) an
    explicit legacy ``tiles`` triple, an explicit ``plan`` (the dispatcher/
    autotuner handoff), or a fresh plan resolved for the context's target
    (tuned winner when one is stored). ``target=``/``tiles=`` are legacy
    (DeprecationWarning; lint VRF015)."""
    warn_legacy_kernel_kwargs("matmul", target=target, tiles=tiles)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, f"inner dims mismatch: {k} vs {k2}"
    in_bits = jnp.dtype(a.dtype).itemsize * 8
    (bm, bn, bk), tgt = resolve_kernel_plan(
        _matmul_spec(m, n, k, in_bits),
        plan=plan, target=target, tiles=tiles, ctx=ctx)

    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))

    nm, nn, nk = mp // bm, np_ // bn, kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk, bm=bm, bn=bn, bk=bk),
        grid=(nm, nn, nk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bm, bk), a.dtype),  # double-buffered A stream
            pltpu.VMEM((2, bk, bn), b.dtype),  # double-buffered B stream
            pltpu.VMEM((bm, bn), jnp.float32),  # f32 accumulator
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tgt.vmem_limit_bytes),
        interpret=pallas_interpret(),
    )(a, b)
    return out[:m, :n]


def matmul_access_plan(
    a,  # array or ShapeDtypeStruct, (m, k)
    b,  # array or ShapeDtypeStruct, (k, n)
    tiles: Optional[Tuple[int, int, int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
    op: str = "matmul",
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one ``matmul``
    launch: the A/B halo-free DMA windows streamed every (i, j, k) step, the
    blocked output store, the two-slot VMEM scratch, and the double-buffered
    DMA schedule over the k reduction — restated from the same geometry the
    kernel lowers so ``repro.verify.audit`` can cross-check ``words_fn``."""
    from repro.verify.access import (BlockAccess, KernelAccessPlan,
                                     ScratchAlloc, WindowAccess)
    from repro.verify.hazards import double_buffered_schedule

    m, k = a.shape
    n = b.shape[1]
    in_bits = jnp.dtype(a.dtype).itemsize * 8
    (bm, bn, bk), _ = resolve_kernel_plan(
        _matmul_spec(m, n, k, in_bits), plan=plan, target=target, tiles=tiles)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    grid = (mp // bm, np_ // bn, kp // bk)
    p_a = jnp.dtype(a.dtype).itemsize / 4.0
    p_b = jnp.dtype(b.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    accesses = (
        WindowAccess(
            name="a", kind="load", array_shape=(mp, kp), word_size=p_a,
            window=lambda i, j, ki: ((i * bm, bm), (ki * bk, bk)),
            requires=lambda i, j, ki: ((i * bm, (i + 1) * bm),
                                       (ki * bk, (ki + 1) * bk))),
        WindowAccess(
            name="b", kind="load", array_shape=(kp, np_), word_size=p_b,
            window=lambda i, j, ki: ((ki * bk, bk), (j * bn, bn)),
            requires=lambda i, j, ki: ((ki * bk, (ki + 1) * bk),
                                       (j * bn, (j + 1) * bn))),
        BlockAccess(
            name="out", kind="store", block_shape=(bm, bn),
            array_shape=(mp, np_), word_size=p_out,
            index_map=lambda i, j, ki: (i, j)),
    )
    scratch = (
        ScratchAlloc("a_vmem[2]", 2 * bm * bk * p_a),
        ScratchAlloc("b_vmem[2]", 2 * bk * bn * p_b),
        ScratchAlloc("acc_f32", float(bm * bn)),
    )
    return KernelAccessPlan(
        op=op, grid=grid, accesses=accesses, scratch=scratch,
        dma=double_buffered_schedule(grid[2], n_slots=2, name="a/b k-stream"),
        note="DMA schedule repeats identically per (i, j) output tile")


def matmul_hbm_words(
    a,  # array or ShapeDtypeStruct, (m, k)
    b,  # array or ShapeDtypeStruct, (k, n)
    tiles: Optional[Tuple[int, int, int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
) -> float:
    """Measured HBM words (32-bit) one ``matmul`` dispatch moves: one A and
    one B block DMA'd per grid step plus the padded output stores. Only
    shapes/dtypes are consulted (``jax.ShapeDtypeStruct`` works)."""
    m, k = a.shape
    n = b.shape[1]
    in_bits = jnp.dtype(a.dtype).itemsize * 8
    (bm, bn, bk), _ = resolve_kernel_plan(
        _matmul_spec(m, n, k, in_bits), plan=plan, target=target, tiles=tiles)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    n_steps = (mp // bm) * (np_ // bn) * (kp // bk)
    p_a = jnp.dtype(a.dtype).itemsize / 4.0
    p_b = jnp.dtype(b.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    return (n_steps * (bm * bk * p_a + bk * bn * p_b) + mp * np_ * p_out)
