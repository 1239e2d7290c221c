"""Quantized (int8-stream) conv2d / matmul Pallas kernels.

Same LP tiling as ``kernels/conv2d.py`` / ``kernels/matmul.py`` (the conv
keeps the NCHW halo-window geometry of ``_launch_geometry`` below, which
Mosaic does not lower yet; the channels-last rework reached only the bf16
conv), but the input and filter
stream HBM->VMEM as int8 — a quarter word per element — so the blocking LP,
solving against ``Precision(0.25, 0.25, p_out)``, buys roughly 2x bigger
tiles from the same VMEM and the Thm 2.1 bound itself drops (see
``core.bounds.mixed_precision_bound``). Inside the kernel each MXU tap runs
an int8 x int8 -> int32 dot (``preferred_element_type``, exact for any
b_cI <= 2^14) whose result is widened into the f32 accumulator tile; the
folded per-output-channel scale — one f32 vector, quantization's whole
dequantization state — is applied once at the store:

    out[n, co, h, w] = (sum_taps int8-dot) * scale[co]  ->  out_dtype

``scale`` is ``s_x * s_w[c_O]`` (``repro.quant.quantize_conv_operands``), a
``(1, c_O)`` f32 operand delivered through a constant-index BlockSpec: Pallas
fetches it exactly once per launch, which is also exactly how the words_fn
and the access plan charge it (c_O words, not c_O x n_steps — the seeded
``scale_applied_twice`` mutant flips precisely this and the auditor must
catch it).

Output storage defaults to bf16 (half a word): int8-in/bf16-out is the
policy ``repro.quant.INT8_SPEC`` names, and it is what moves measured conv
words to ~0.5x the bf16-in/f32-out baseline on the ResNet-50 shapes
(gated <= 0.55x in ``benchmarks/quant_bench.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_model import Precision, round_up
from repro.plan import (ConvSpec, ExecutionPlan, HardwareTarget, MatmulSpec,
                        pallas_interpret, resolve_kernel_plan,
                        warn_legacy_kernel_kwargs)


def _normalize_tiles(tiles: Sequence[int], h_O: int, w_O: int
                     ) -> Tuple[int, int, int, int, int]:
    """Accept the legacy (bN, b_cI, b_cO) triple (spatial kept whole) or the
    full (bN, b_cI, b_cO, b_hO, b_wO) planner tuple."""
    if len(tiles) == 3:
        return (*tiles, h_O, w_O)
    bN, b_cI, b_cO, bh, bw = tiles
    return (bN, b_cI, b_cO, max(1, min(bh, h_O)), max(1, min(bw, w_O)))


def _launch_geometry(N: int, c_I: int, c_O: int, H: int, W: int, h_F: int,
                     w_F: int, sh: int, sw: int,
                     tiles: Tuple[int, int, int, int, int]):
    """Padded dims, halo-window extents, and grid of the NCHW int8 conv —
    the single source of truth shared by its lowering and its counters."""
    bN, b_cI, b_cO, bh, bw = tiles
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    Np, cIp, cOp = round_up(N, bN), round_up(c_I, b_cI), round_up(c_O, b_cO)
    hOp, wOp = round_up(h_O, bh), round_up(w_O, bw)
    # padded input must cover the last block's halo window
    Hp = max(H, (hOp - 1) * sh + h_F)
    Wp = max(W, (wOp - 1) * sw + w_F)
    h_in = (bh - 1) * sh + h_F
    w_in = (bw - 1) * sw + w_F
    grid = (Np // bN, cOp // b_cO, hOp // bh, wOp // bw, cIp // b_cI)
    return Np, cIp, cOp, hOp, wOp, Hp, Wp, h_in, w_in, grid


def _wordwidth(dtype) -> float:
    return jnp.dtype(dtype).itemsize / 4.0


def _conv_spec_q(N: int, c_I: int, c_O: int, h_O: int, w_O: int, h_F: int,
                 w_F: int, sh: int, sw: int, x_dtype, w_dtype,
                 out_dtype) -> ConvSpec:
    """Per-operand mixed-precision ConvSpec: the LP and the Thm 2.1 bound
    both see the stored widths (int8 = 0.25 words), unlike ``_conv_spec``
    which pins p_O to one full word."""
    return ConvSpec(N=N, c_I=c_I, c_O=c_O, w_O=w_O, h_O=h_O, w_F=w_F,
                    h_F=h_F, sw=sw, sh=sh,
                    prec=Precision(_wordwidth(x_dtype), _wordwidth(w_dtype),
                                   _wordwidth(out_dtype)))


def _matmul_spec_q(m: int, n: int, k: int, a_dtype, b_dtype,
                   out_dtype) -> MatmulSpec:
    return MatmulSpec(m=m, n=n, k=k,
                      prec=Precision(_wordwidth(a_dtype),
                                     _wordwidth(b_dtype),
                                     _wordwidth(out_dtype)))


# ---------------------------------------------------------------------------
# conv2d_q
# ---------------------------------------------------------------------------

def _conv_q_kernel(x_hbm, w_hbm, s_ref, o_ref, x_vmem, w_vmem, acc_ref,
                   sems, *, n_ci: int,
                   tiles: Tuple[int, int, int, int, int], h_in: int,
                   w_in: int, h_F: int, w_F: int, sh: int, sw: int):
    bN, b_cI, b_cO, bh, bw = tiles
    n, co, h, wb, ci = (pl.program_id(i) for i in range(5))

    def stream(slot, ci_idx):
        return (
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(n * bN, bN), pl.ds(ci_idx * b_cI, b_cI),
                         pl.ds(h * bh * sh, h_in), pl.ds(wb * bw * sw, w_in)],
                x_vmem.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(
                w_hbm.at[pl.ds(co * b_cO, b_cO), pl.ds(ci_idx * b_cI, b_cI)],
                w_vmem.at[slot], sems.at[slot, 1]),
        )

    @pl.when(ci == 0)
    def _warmup():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in stream(0, 0):
            cp.start()

    slot = ci % 2

    @pl.when(ci + 1 < n_ci)
    def _prefetch():
        for cp in stream(1 - slot, ci + 1):
            cp.start()

    for cp in stream(slot, ci):
        cp.wait()

    x = x_vmem[slot]  # (bN, b_cI, h_in, w_in) int8
    w = w_vmem[slot]  # (b_cO, b_cI, h_F, w_F) int8
    acc = acc_ref[...]
    for hf in range(h_F):
        for wf in range(w_F):
            tap = jax.lax.slice(
                x,
                (0, 0, hf, wf),
                (bN, b_cI, hf + (bh - 1) * sh + 1, wf + (bw - 1) * sw + 1),
                (1, 1, sh, sw),
            )
            lhs = tap.transpose(0, 2, 3, 1).reshape(bN * bh * bw, b_cI)
            rhs = w[:, :, hf, wf].T  # (b_cI, b_cO)
            # exact int8 x int8 -> int32 tap product, widened into the f32
            # accumulator (never narrowed below f32 until the scaled store)
            out = jnp.dot(lhs, rhs, preferred_element_type=jnp.int32)
            acc = acc + out.astype(jnp.float32).reshape(
                bN, bh, bw, b_cO).transpose(0, 3, 1, 2)
    acc_ref[...] = acc

    @pl.when(ci == n_ci - 1)
    def _store():
        s = s_ref[0, pl.ds(co * b_cO, b_cO)]  # folded per-c_O scales
        o_ref[...] = (acc_ref[...] * s[None, :, None, None]).astype(
            o_ref.dtype)


def conv2d_q(
    x: jax.Array,  # (N, c_I, H, W) int8
    w: jax.Array,  # (c_O, c_I, h_F, w_F) int8
    scale: jax.Array,  # (1, c_O) f32: folded s_x * s_w[c_O]
    stride: Tuple[int, int] = (1, 1),
    out_dtype=jnp.bfloat16,
    ctx=None,  # ExecutionContext (duck-typed: .target/.autotune)
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """Quantized direct convolution (VALID padding): int8 operand streams,
    f32 accumulation, one folded per-output-channel scale applied at the
    store. Operands come from ``repro.quant.quantize_conv_operands``.
    Execution policy rides ``ctx``; ``target=``/``tiles=`` are legacy
    (DeprecationWarning; lint VRF015)."""
    warn_legacy_kernel_kwargs("conv2d_q", target=target, tiles=tiles)
    N, c_I, H, W = x.shape
    c_O, c_I2, h_F, w_F = w.shape
    assert c_I == c_I2
    assert scale.shape == (1, c_O), f"scale must be (1, {c_O}), got {scale.shape}"
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    t, tgt = resolve_kernel_plan(
        _conv_spec_q(N, c_I, c_O, h_O, w_O, h_F, w_F, sh, sw, x.dtype,
                     w.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles, ctx=ctx)
    t = _normalize_tiles(t, h_O, w_O)
    bN, b_cI, b_cO, bh, bw = t
    (Np, cIp, cOp, hOp, wOp, Hp, Wp, h_in, w_in,
     grid) = _launch_geometry(N, c_I, c_O, H, W, h_F, w_F, sh, sw, t)

    if (Np, cIp, Hp, Wp) != (N, c_I, H, W):
        x = jnp.pad(x, ((0, Np - N), (0, cIp - c_I), (0, Hp - H),
                        (0, Wp - W)))
    if (cOp, cIp) != (c_O, c_I):
        w = jnp.pad(w, ((0, cOp - c_O), (0, cIp - c_I), (0, 0), (0, 0)))
    if cOp != c_O:
        scale = jnp.pad(scale, ((0, 0), (0, cOp - c_O)))

    out = pl.pallas_call(
        functools.partial(_conv_q_kernel, n_ci=grid[4], tiles=t, h_in=h_in,
                          w_in=w_in, h_F=h_F, w_F=w_F, sh=sh, sw=sw),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # constant index map: Pallas fetches the scale vector exactly
            # once per launch (c_O words — what words_fn charges)
            pl.BlockSpec((1, cOp), lambda n, co, h, wb, ci: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bN, b_cO, bh, bw),
                               lambda n, co, h, wb, ci: (n, co, h, wb)),
        out_shape=jax.ShapeDtypeStruct((Np, cOp, hOp, wOp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bN, b_cI, h_in, w_in), x.dtype),  # int8 stream
            pltpu.VMEM((2, b_cO, b_cI, h_F, w_F), w.dtype),  # int8 stream
            pltpu.VMEM((bN, b_cO, bh, bw), jnp.float32),  # f32 accumulator
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tgt.vmem_limit_bytes),
        interpret=pallas_interpret(),
    )(x, w, scale)
    return out[:N, :c_O, :h_O, :w_O]


def conv2d_q_access_plan(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W) int8
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F) int8
    scale=None,  # array or ShapeDtypeStruct, (1, c_O) f32
    stride: Tuple[int, int] = (1, 1),
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.bfloat16,
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one ``conv2d_q``
    launch. Identical stream structure to ``conv2d_access_plan`` at int8
    word widths, plus the scale vector as a constant-index BlockAccess —
    the auditor's revisit elision counts its c_O words exactly once."""
    from repro.verify.access import (BlockAccess, KernelAccessPlan,
                                     ScratchAlloc, WindowAccess)
    from repro.verify.hazards import double_buffered_schedule

    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    t, _ = resolve_kernel_plan(
        _conv_spec_q(N, c_I, c_O, h_O, w_O, h_F, w_F, sh, sw, x.dtype,
                     w.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles)
    t = _normalize_tiles(t, h_O, w_O)
    bN, b_cI, b_cO, bh, bw = t
    (Np, cIp, cOp, hOp, wOp, Hp, Wp, h_in, w_in,
     grid) = _launch_geometry(N, c_I, c_O, H, W, h_F, w_F, sh, sw, t)
    p_in = _wordwidth(x.dtype)
    p_flt = _wordwidth(w.dtype)
    p_out = _wordwidth(out_dtype)

    def x_requires(n, co, h, wb, ci):
        row_lo, row_hi = h * bh, h * bh + bh - 1
        col_lo, col_hi = wb * bw, wb * bw + bw - 1
        return ((n * bN, (n + 1) * bN),
                (ci * b_cI, (ci + 1) * b_cI),
                (row_lo * sh, row_hi * sh + h_F),
                (col_lo * sw, col_hi * sw + w_F))

    accesses = (
        WindowAccess(
            name="input", kind="load", array_shape=(Np, cIp, Hp, Wp),
            word_size=p_in,
            window=lambda n, co, h, wb, ci: (
                (n * bN, bN), (ci * b_cI, b_cI),
                (h * bh * sh, h_in), (wb * bw * sw, w_in)),
            requires=x_requires),
        WindowAccess(
            name="filter", kind="load", array_shape=(cOp, cIp, h_F, w_F),
            word_size=p_flt,
            window=lambda n, co, h, wb, ci: (
                (co * b_cO, b_cO), (ci * b_cI, b_cI), (0, h_F), (0, w_F)),
            requires=lambda n, co, h, wb, ci: (
                (co * b_cO, (co + 1) * b_cO), (ci * b_cI, (ci + 1) * b_cI),
                (0, h_F), (0, w_F))),
        BlockAccess(
            name="scale", kind="load", block_shape=(1, cOp),
            array_shape=(1, cOp), word_size=1.0,
            index_map=lambda n, co, h, wb, ci: (0, 0),
            note="folded per-c_O dequant scales, fetched once per launch"),
        BlockAccess(
            name="output", kind="store", block_shape=(bN, b_cO, bh, bw),
            array_shape=(Np, cOp, hOp, wOp), word_size=p_out,
            index_map=lambda n, co, h, wb, ci: (n, co, h, wb)),
    )
    scratch = (
        ScratchAlloc("x_vmem[2]", 2 * bN * b_cI * h_in * w_in * p_in),
        ScratchAlloc("w_vmem[2]", 2 * b_cO * b_cI * h_F * w_F * p_flt),
        ScratchAlloc("acc_f32", float(bN * b_cO * bh * bw)),
    )
    return KernelAccessPlan(
        op="conv2d_q", grid=grid, accesses=accesses, scratch=scratch,
        dma=double_buffered_schedule(grid[4], n_slots=2,
                                     name="int8 input/filter c_I stream"),
        note="DMA schedule repeats identically per (n, co, h, w) tile")


def conv2d_q_hbm_words(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W) int8
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F) int8
    scale=None,  # unused beyond its c_O words; keeps the spec_args signature
    stride: Tuple[int, int] = (1, 1),
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.bfloat16,
) -> float:
    """Measured HBM words of one ``conv2d_q`` dispatch: int8 input/filter
    windows per grid step, the padded out_dtype stores, plus the scale
    vector exactly once (c_O f32 words)."""
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    t, _ = resolve_kernel_plan(
        _conv_spec_q(N, c_I, c_O, h_O, w_O, h_F, w_F, sh, sw, x.dtype,
                     w.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles)
    t = _normalize_tiles(t, h_O, w_O)
    bN, b_cI, b_cO, bh, bw = t
    (Np, cIp, cOp, hOp, wOp, _, _, h_in, w_in,
     grid) = _launch_geometry(N, c_I, c_O, H, W, h_F, w_F, sh, sw, t)
    n_steps = math.prod(grid)
    p_in = _wordwidth(x.dtype)
    p_flt = _wordwidth(w.dtype)
    p_out = _wordwidth(out_dtype)
    return (n_steps * bN * b_cI * h_in * w_in * p_in
            + n_steps * b_cO * b_cI * h_F * w_F * p_flt
            + Np * cOp * hOp * wOp * p_out
            + cOp * 1.0)


# ---------------------------------------------------------------------------
# matmul_q
# ---------------------------------------------------------------------------

def _matmul_q_kernel(a_hbm, b_hbm, s_ref, o_ref, a_vmem, b_vmem, acc_ref,
                     sems, *, nk: int, bm: int, bn: int, bk: int):
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
    def _prefetch():
        for cp in stream(1 - slot, ki + 1):
            cp.start()

    for cp in stream(slot, ki):
        cp.wait()

    acc_ref[...] += jnp.dot(
        a_vmem[slot], b_vmem[slot], preferred_element_type=jnp.int32
    ).astype(jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        s = s_ref[0, pl.ds(j * bn, bn)]  # folded per-column scales
        o_ref[...] = (acc_ref[...] * s[None, :]).astype(o_ref.dtype)


def matmul_q(
    a: jax.Array,  # (m, k) int8
    b: jax.Array,  # (k, n) int8
    scale: jax.Array,  # (1, n) f32: folded s_a * s_b[n]
    out_dtype=jnp.bfloat16,
    ctx=None,  # ExecutionContext (duck-typed: .target/.autotune)
    tiles: Optional[Tuple[int, int, int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """Quantized GEMM: int8 A/B streams double-buffered over k, f32
    accumulator, folded per-column scale applied at the store. Operands come
    from ``repro.quant.quantize_matmul_operands``. Execution policy rides
    ``ctx``; ``target=``/``tiles=`` are legacy (DeprecationWarning; lint
    VRF015)."""
    warn_legacy_kernel_kwargs("matmul_q", target=target, tiles=tiles)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, f"inner dims mismatch: {k} vs {k2}"
    assert scale.shape == (1, n), f"scale must be (1, {n}), got {scale.shape}"
    (bm, bn, bk), tgt = resolve_kernel_plan(
        _matmul_spec_q(m, n, k, a.dtype, b.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles, ctx=ctx)

    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    if np_ != n:
        scale = jnp.pad(scale, ((0, 0), (0, np_ - n)))

    nm, nn, nk = mp // bm, np_ // bn, kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_q_kernel, nk=nk, bm=bm, bn=bn, bk=bk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, np_), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bm, bk), a.dtype),  # int8 A stream
            pltpu.VMEM((2, bk, bn), b.dtype),  # int8 B stream
            pltpu.VMEM((bm, bn), jnp.float32),  # f32 accumulator
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tgt.vmem_limit_bytes),
        interpret=pallas_interpret(),
    )(a, b, scale)
    return out[:m, :n]


def matmul_q_access_plan(
    a,  # array or ShapeDtypeStruct, (m, k) int8
    b,  # array or ShapeDtypeStruct, (k, n) int8
    scale=None,
    tiles: Optional[Tuple[int, int, int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.bfloat16,
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one ``matmul_q``
    launch: ``matmul_access_plan``'s structure at int8 word widths plus the
    constant-index scale BlockAccess (counted once)."""
    from repro.verify.access import (BlockAccess, KernelAccessPlan,
                                     ScratchAlloc, WindowAccess)
    from repro.verify.hazards import double_buffered_schedule

    m, k = a.shape
    n = b.shape[1]
    (bm, bn, bk), _ = resolve_kernel_plan(
        _matmul_spec_q(m, n, k, a.dtype, b.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    grid = (mp // bm, np_ // bn, kp // bk)
    p_a = _wordwidth(a.dtype)
    p_b = _wordwidth(b.dtype)
    p_out = _wordwidth(out_dtype)
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
            name="scale", kind="load", block_shape=(1, np_),
            array_shape=(1, np_), word_size=1.0,
            index_map=lambda i, j, ki: (0, 0),
            note="folded per-column dequant scales, fetched once per launch"),
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
        op="matmul_q", grid=grid, accesses=accesses, scratch=scratch,
        dma=double_buffered_schedule(grid[2], n_slots=2,
                                     name="int8 a/b k-stream"),
        note="DMA schedule repeats identically per (i, j) output tile")


def matmul_q_hbm_words(
    a,  # array or ShapeDtypeStruct, (m, k) int8
    b,  # array or ShapeDtypeStruct, (k, n) int8
    scale=None,
    tiles: Optional[Tuple[int, int, int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.bfloat16,
) -> float:
    """Measured HBM words of one ``matmul_q`` dispatch (int8 streams +
    out_dtype stores + the scale vector once)."""
    m, k = a.shape
    n = b.shape[1]
    (bm, bn, bk), _ = resolve_kernel_plan(
        _matmul_spec_q(m, n, k, a.dtype, b.dtype, out_dtype),
        plan=plan, target=target, tiles=tiles)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    n_steps = (mp // bm) * (np_ // bn) * (kp // bk)
    p_a = _wordwidth(a.dtype)
    p_b = _wordwidth(b.dtype)
    p_out = _wordwidth(out_dtype)
    return (n_steps * (bm * bk * p_a + bk * bn * p_b)
            + mp * np_ * p_out + np_ * 1.0)
