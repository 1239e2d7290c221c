"""Communication-optimal direct convolution as a Pallas TPU kernel.

This is the paper's §5 tiling, retargeted from GEMMINI to the TPU memory
hierarchy: the blocking LP (core.tiling.optimize_blocking, eq. 6 + the §5
buffer model) picks the channel/batch/spatial tile sizes; the f32 output tile
plays the accumulator (held in VMEM across the c_I reduction, which is the
innermost grid axis); input/filter tiles stream HBM->VMEM in low precision.

Public contract: NCHW input, OIHW filter, VALID padding, arbitrary stride —
the exact 7NL CNN of §2.1. Inside, the kernel works channels-last, because
the TPU tiles the two minor dims of every array by (sublane, lane) and can
only slice whole tiles:

  * one XLA layout pass (counted by ``conv2d_hbm_words``) turns the input
    into space-to-depth (H, W, N, C): the s_h x s_w stride phases are folded
    into the channel axis, so the strided conv becomes a stride-1 conv with
    ceil(h_F/s_h) x ceil(w_F/s_w) taps and no tap is a strided slice; the
    filter becomes (taps_h, taps_w, channels, c_O) to match;
  * where one reduction block holds every channel and the column taps fit
    one lane tile beside them (ResNet-50's stem: 12 channels x 4 taps),
    the column taps ride the lanes too (``ConvKernelGeometry.fold``), so
    the kernel runs ceil(h_F/s_h) x 1 taps and its window has no column
    halo;
  * channels ride the lanes (padded to whole lanes), the batch rides the
    sublanes (blocked in whole sublane tiles), and both spatial axes lead,
    untiled — so the halo windows start at any row and column, and a tap is
    a window at a static (row, column) offset that collapses, with no
    relayout, into one MXU GEMM (pixels * bN, b_c) x (b_c, b_cO);
  * a second layout pass crops the padding and returns NCHW.

Spatial tiling is halo-aware: an output row block [i*bh, (i+1)*bh) needs the
overlapping input window of bh + taps_h - 1 space-to-depth rows, and
similarly for columns. Overlapping windows cannot be expressed with blocked
BlockSpecs, so the input and filter stay in ANY/HBM memory and the kernel
streams each window itself with ``pltpu.make_async_copy`` into a two-slot
VMEM scratch, double-buffered over the whole grid, taken in order: while the
MXU runs the taps of one grid step, the DMAs for the next are already in
flight, whether that step is the next c_I block or the next tile (§5's
double-buffering, which is also why the LP halves usable capacity). The
grid's axes therefore run in order, on one core.

``conv2d_hbm_words`` reports the measured HBM words one dispatch moves,
computed from the same launch geometry the kernel lowers (layout passes +
grid x DMA window sizes + output stores) — the number ``ops.explain``
places next to the paper's Thm 2.1 lower bound.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_model import ConvShape, Precision
from repro.core.tiling import ConvKernelGeometry, conv_kernel_geometry
from repro.plan import (ConvSpec, ExecutionPlan, HardwareTarget,
                        pallas_interpret, resolve_kernel_plan,
                        warn_legacy_kernel_kwargs)

# Rows of one MXU GEMM per tap: the kernel walks its output tile in chunks of
# output pixels x bN holding about this many rows, so the tap operand and
# the f32 partial sum stay a few hundred KiB whatever the tile size.
_GEMM_ROWS = 512


def _conv_spec(N: int, c_I: int, c_O: int, h_O: int, w_O: int, h_F: int,
               w_F: int, sh: int, sw: int, in_bits: int) -> ConvSpec:
    p_in = in_bits / 32.0
    return ConvSpec(N=N, c_I=c_I, c_O=c_O, w_O=w_O, h_O=h_O, w_F=w_F, h_F=h_F,
                    sw=sw, sh=sh, prec=Precision(p_in, p_in, 1.0))


def _geometry(x, w, stride, plan=None, target=None, tiles=None, ctx=None
              ) -> Tuple[ConvShape, ConvKernelGeometry, HardwareTarget]:
    """Resolve tiles for these operands and lay out the launch."""
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    spec = _conv_spec(N, c_I, c_O, h_O, w_O, h_F, w_F, sh, sw,
                      jnp.dtype(x.dtype).itemsize * 8)
    t, tgt = resolve_kernel_plan(spec, plan=plan, target=target, tiles=tiles,
                                 ctx=ctx)
    if len(t) == 3:  # legacy (bN, b_cI, b_cO) triple: spatial kept whole
        t = (*t, h_O, w_O)
    shape = spec.to_shape(spec.prec)
    g = conv_kernel_geometry(shape, t, tgt.align)
    return shape, g, tgt


def _input_layout(x: jax.Array, shape: ConvShape, g: ConvKernelGeometry
                  ) -> jax.Array:
    """NCHW -> padded space-to-depth (Hp, Wp, Np, Cp); the channel order
    (c, phase_h, phase_w) keeps each c_I block contiguous. Folded, lane
    (tw, c, phase_h, phase_w) of column j holds column j + tw: a one-hot
    convolution gathers each (s_h, fold * s_w) window straight into the
    kernel's lane-padded layout, so XLA writes that array once (shifted
    copies concatenated on the lane axis are each laid out lane-padded
    first, ten times their size at ResNet-50's stem)."""
    N, C, H, W = x.shape
    sh, sw = shape.sh, shape.sw
    rows, cols = g.Hp * sh, (g.Wp + g.fold - 1) * sw
    cpad = g.grid[4] * g.tiles[1]
    x = x[:, :, :min(H, rows), :min(W, cols)]
    x = jnp.pad(x, ((0, g.Np - N), (0, cpad - C), (0, rows - x.shape[2]),
                    (0, cols - x.shape[3])))
    if g.fold > 1:
        lanes = g.fold * cpad * sh * sw
        taps = np.eye(g.Cp, lanes, dtype=np.float32).reshape(
            g.Cp, g.fold, cpad, sh, sw).transpose(0, 2, 3, 1, 4)
        return jax.lax.conv_general_dilated(
            x, jnp.asarray(taps.reshape(g.Cp, cpad, sh, g.fold * sw),
                           x.dtype),
            window_strides=(sh, sw), padding="VALID",
            dimension_numbers=("NCHW", "OIHW", "HWNC"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=x.dtype)
    x = x.reshape(g.Np, cpad, g.Hp, sh, g.Wp, sw).transpose(2, 4, 0, 1, 3, 5)
    x = x.reshape(g.Hp, g.Wp, g.Np, cpad * sh * sw)
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, g.Cp - x.shape[3])))


def _filter_layout(w: jax.Array, shape: ConvShape, g: ConvKernelGeometry
                   ) -> jax.Array:
    """OIHW -> (taps_h, taps_w, Cp, cOp), matching :func:`_input_layout`:
    folded column taps lead the lanes, as (tw, ch)."""
    c_O, C, h_F, w_F = w.shape
    sh, sw = shape.sh, shape.sw
    cpad = g.grid[4] * g.tiles[1]
    taps_w = g.wF * g.fold
    w = jnp.pad(w, ((0, g.cOp - c_O), (0, cpad - C), (0, g.hF * sh - h_F),
                    (0, taps_w * sw - w_F)))
    w = w.reshape(g.cOp, cpad, g.hF, sh, taps_w, sw).transpose(
        2, 4, 1, 3, 5, 0)
    w = w.reshape(g.hF, g.wF, g.fold * cpad * sh * sw, g.cOp)
    return jnp.pad(w, ((0, 0), (0, 0), (0, g.Cp - w.shape[2]), (0, 0)))


def _gemm_block(bh: int, bw: int, bN: int) -> Tuple[int, int]:
    """(rows, cols) of output pixels per tap GEMM: whole rows of the tile
    while ``rows * bw * bN`` stays within ``_GEMM_ROWS``, else part of one
    row (at least one pixel)."""
    if bw * bN <= _GEMM_ROWS:
        return max(r for r in range(1, bh + 1)
                   if bh % r == 0 and r * bw * bN <= _GEMM_ROWS), bw
    return 1, max(c for c in range(1, bw + 1)
                  if bw % c == 0 and (c == 1 or c * bN <= _GEMM_ROWS))


def _grid_index(step, grid: Sequence[int]):
    """The grid indices of linear step ``step`` (row-major, the order the
    grid runs in)."""
    idx = []
    for extent in reversed(grid):
        idx.append(step % extent)
        step = step // extent
    return tuple(reversed(idx))


def _conv_kernel(x_hbm, w_hbm, o_ref, x_vmem, w_vmem, acc_ref, sems, *,
                 g: ConvKernelGeometry):
    bN, _, b_cO, bh, bw = g.tiles
    b_c, n_ci = g.b_c, g.grid[4]
    idx = tuple(pl.program_id(i) for i in range(5))
    ci = idx[4]
    step = 0
    for i, extent in zip(idx, g.grid):
        step = step * extent + i
    slot = step % 2

    def stream(slot, n, co, h, wb, ci):
        """The two HBM->VMEM copies feeding grid step (n, co, h, wb, ci):
        the halo input window of the (n, h, wb) tile's c_I block ci and the
        (co, ci) filter block."""
        return (
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(h * bh, g.h_in), pl.ds(wb * bw, g.w_in),
                         pl.ds(n * bN, bN), pl.ds(ci * b_c, b_c)],
                x_vmem.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(
                w_hbm.at[:, :, pl.ds(ci * b_c, b_c),
                         pl.ds(co * b_cO, b_cO)],
                w_vmem.at[slot], sems.at[slot, 1]),
        )

    @pl.when(step == 0)
    def _warmup():
        for cp in stream(0, *idx):
            cp.start()

    @pl.when(step + 1 < math.prod(g.grid))
    def _prefetch():  # overlap the next grid step's DMAs with the GEMMs
        for cp in stream(1 - slot, *_grid_index(step + 1, g.grid)):
            cp.start()

    @pl.when(ci == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for cp in stream(slot, *idx):
        cp.wait()

    rows, cols = _gemm_block(bh, bw, bN)
    per_row = bw // cols

    def chunk(t, carry):
        r0, c0 = (t // per_row) * rows, (t % per_row) * cols
        part = None
        for hf in range(g.hF):
            for wf in range(g.wF):
                # MXU GEMM: (rows * cols * bN, b_c) @ (b_c, b_cO)
                tap = x_vmem[slot, pl.ds(r0 + hf, rows), pl.ds(c0 + wf, cols)]
                out = jnp.dot(tap.reshape(rows * cols * bN, b_c),
                              w_vmem[slot, hf, wf],
                              preferred_element_type=jnp.float32)
                part = out if part is None else part + out
        acc_ref[pl.ds(r0, rows), pl.ds(c0, cols)] += part.reshape(
            rows, cols, bN, b_cO)
        return carry

    jax.lax.fori_loop(0, (bh // rows) * per_row, chunk, 0)

    @pl.when(ci == n_ci - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def conv2d(
    x: jax.Array,  # (N, c_I, H, W)
    w: jax.Array,  # (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    out_dtype=jnp.float32,
    ctx=None,  # ExecutionContext (duck-typed: .target/.autotune)
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """Direct convolution with paper-LP tiling. VALID padding.

    Execution policy rides ``ctx`` (an ``repro.ops.ExecutionContext``:
    target, autotune policy). Tiles come from (in priority order) an
    explicit legacy ``tiles`` tuple — (bN, b_cI, b_cO) or
    (bN, b_cI, b_cO, b_hO, b_wO) — an explicit ``plan``
    (:class:`repro.plan.ExecutionPlan`, the dispatcher/autotuner handoff),
    or a fresh plan resolved for the context's target (the attached
    device's preset by default; tuned winner when one is stored). Tiles are
    snapped to the target's layout (``core.tiling.conv_kernel_tiles``).
    ``target=``/``tiles=`` are legacy (DeprecationWarning; lint VRF015).
    """
    warn_legacy_kernel_kwargs("conv2d", target=target, tiles=tiles)
    assert x.shape[1] == w.shape[1]
    shape, g, tgt = _geometry(x, w, stride, plan=plan, target=target,
                              tiles=tiles, ctx=ctx)
    bN, _, b_cO, bh, bw = g.tiles
    out = pl.pallas_call(
        functools.partial(_conv_kernel, g=g),
        grid=g.grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bh, bw, bN, b_cO),
                               lambda n, co, h, wb, ci: (h, wb, n, co)),
        out_shape=jax.ShapeDtypeStruct((g.hOp, g.wOp, g.Np, g.cOp),
                                       out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, g.h_in, g.w_in, bN, g.b_c), x.dtype),  # double-
            pltpu.VMEM((2, g.hF, g.wF, g.b_c, b_cO), w.dtype),  # buffered
            pltpu.VMEM((bh, bw, bN, b_cO), jnp.float32),  # f32 accumulator
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 5,
            vmem_limit_bytes=tgt.vmem_limit_bytes),
        interpret=pallas_interpret(),
        name="conv2d_lp",
    )(_input_layout(x, shape, g), _filter_layout(w, shape, g))
    out = out[:shape.h_O, :shape.w_O, :shape.N, :shape.c_O]
    return out.transpose(2, 3, 0, 1)


def exact_window(H: int, W: int, h_F: int, w_F: int, sh: int, sw: int
                 ) -> bool:
    """True iff an (H, W) input extent is an *exact* halo window — every row
    and column participates in some VALID output ((H - h_F) % sh == 0 and
    likewise for W). Shard-local windows built by ``repro.distributed`` are
    exact by construction; an inexact window there means halo rows were
    mis-exchanged, so the distributed path asserts this before dispatch."""
    return (H - h_F) % sh == 0 and (W - w_F) % sw == 0


def conv2d_shard(
    x: jax.Array,  # (bN, b_cI, (b_hO-1)*sh + h_F, (b_wO-1)*sw + w_F)
    w: jax.Array,  # (c_O, b_cI, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    out_dtype=jnp.float32,
    ctx=None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """Shard-local entry for ``repro.distributed``: the same LP-tiled kernel
    as :func:`conv2d`, but the input must be an exact halo window (the shape
    each shard assembles after its ``ppermute`` exchanges — no dead rows).
    Plans resolve for the *local* shape, so each shard tiles its own block."""
    warn_legacy_kernel_kwargs("conv2d_shard", target=target)
    if ctx is None and target is not None:
        # absorb the legacy kwarg here so the inner conv2d doesn't re-warn
        from types import SimpleNamespace
        ctx = SimpleNamespace(target=target, autotune=None)
    N, c_I, H, W = x.shape
    _, _, h_F, w_F = w.shape
    sh, sw = stride
    if not exact_window(H, W, h_F, w_F, sh, sw):
        raise ValueError(
            f"shard-local conv window ({H}, {W}) is not exact for filter "
            f"({h_F}, {w_F}) stride ({sh}, {sw}): halo rows were "
            "mis-exchanged upstream")
    return conv2d(x, w, stride=stride, out_dtype=out_dtype, ctx=ctx,
                  plan=plan)


def _layout_words(x, w, shape: ConvShape, g: ConvKernelGeometry, out_dtype
                  ) -> Tuple[float, float, float]:
    """HBM words of the three XLA layout passes around the kernel: input to
    space-to-depth HWNC (read what is kept, write the padded copy, column
    taps folded in or not), filter to (taps, Cp, cOp), and the output crop
    + NCHW transpose."""
    N, C, H, W = x.shape
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    p_flt = jnp.dtype(w.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    cols = g.Wp + g.fold - 1  # space-to-depth columns the layout reads
    kept = N * C * min(H, g.Hp * shape.sh) * min(W, cols * shape.sw)
    x_words = p_in * (kept + g.Np * g.Hp * g.Wp * g.Cp)
    w_words = p_flt * (math.prod(w.shape) + g.hF * g.wF * g.Cp * g.cOp)
    o_words = 2.0 * p_out * shape.N * shape.c_O * shape.h_O * shape.w_O
    return x_words, w_words, o_words


def conv2d_access_plan(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W)
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one ``conv2d``
    launch, restated from :func:`repro.core.tiling.conv_kernel_geometry`.

    The input's ``requires`` region is derived *independently* of the DMA
    window — from the output rows of the tile through the pixel arithmetic
    (output row o, tap hf reads pixel row o*sh + hf, which lives in
    space-to-depth row (o*sh + hf) // sh) — so an off-by-one halo window
    fails the auditor's coverage check even though its word count is
    unchanged. The layout passes are flat, counted accesses."""
    from repro.verify.access import (BlockAccess, FlatAccess,
                                     KernelAccessPlan, ScratchAlloc,
                                     WindowAccess)
    from repro.verify.hazards import double_buffered_schedule

    shape, g, _ = _geometry(x, w, stride, plan=plan, target=target,
                            tiles=tiles)
    bN, _, b_cO, bh, bw = g.tiles
    b_c, sh, sw = g.b_c, shape.sh, shape.sw
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    p_flt = jnp.dtype(w.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    x_layout, w_layout, o_layout = _layout_words(x, w, shape, g, out_dtype)

    def x_requires(n, co, h, wb, ci):
        # first/last output row of the tile -> last pixel row of its taps;
        # folded column j holds space-to-depth columns j .. j + fold - 1
        row_lo, row_hi = h * bh, h * bh + bh - 1
        col_lo, col_hi = wb * bw, wb * bw + bw - 1
        return ((row_lo, (row_hi * sh + shape.h_F - 1) // sh + 1),
                (col_lo, (col_hi * sw + shape.w_F - 1) // sw + 2 - g.fold),
                (n * bN, (n + 1) * bN),
                (ci * b_c, (ci + 1) * b_c))

    accesses = (
        FlatAccess(name="input layout (NCHW -> space-to-depth HWNC)",
                   kind="load", words=x_layout),
        FlatAccess(name="filter layout (OIHW -> taps x Cp x cOp)",
                   kind="load", words=w_layout),
        WindowAccess(
            name="input", kind="load", array_shape=(g.Hp, g.Wp, g.Np, g.Cp),
            word_size=p_in,
            window=lambda n, co, h, wb, ci: (
                (h * bh, g.h_in), (wb * bw, g.w_in), (n * bN, bN),
                (ci * b_c, b_c)),
            requires=x_requires),
        WindowAccess(
            name="filter", kind="load", array_shape=(g.hF, g.wF, g.Cp, g.cOp),
            word_size=p_flt,
            window=lambda n, co, h, wb, ci: (
                (0, g.hF), (0, g.wF), (ci * b_c, b_c), (co * b_cO, b_cO)),
            requires=lambda n, co, h, wb, ci: (
                (0, g.hF), (0, g.wF), (ci * b_c, (ci + 1) * b_c),
                (co * b_cO, (co + 1) * b_cO))),
        BlockAccess(
            name="output", kind="store", block_shape=(bh, bw, bN, b_cO),
            array_shape=(g.hOp, g.wOp, g.Np, g.cOp), word_size=p_out,
            index_map=lambda n, co, h, wb, ci: (h, wb, n, co)),
        FlatAccess(name="output layout (crop + HWNC -> NCHW)", kind="store",
                   words=o_layout),
    )
    scratch = (
        ScratchAlloc("x_vmem[2]", 2 * bN * g.h_in * g.w_in * b_c * p_in),
        ScratchAlloc("w_vmem[2]", 2 * g.hF * g.wF * b_c * b_cO * p_flt),
        ScratchAlloc("acc_f32", float(bN * bh * bw * b_cO)),
    )
    return KernelAccessPlan(
        op="conv2d", grid=g.grid, accesses=accesses, scratch=scratch,
        dma=double_buffered_schedule(math.prod(g.grid), n_slots=2,
                                     name="input/filter stream"),
        note="one DMA stream over the whole grid, in order: each step "
             "prefetches the next step's window, across tiles too")


def conv2d_hbm_words(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W)
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
) -> float:
    """Measured HBM words (32-bit) one ``conv2d`` dispatch moves.

    Counts exactly what the kernel lowers for these arguments: the layout
    passes, one input halo window + one filter block DMA'd per grid step,
    and one output block stored per (n, co, h, w) tile — padding included.
    Only shapes/dtypes are consulted, so ``jax.ShapeDtypeStruct`` arguments
    work (no execution)."""
    shape, g, _ = _geometry(x, w, stride, plan=plan, target=target,
                            tiles=tiles)
    bN, _, b_cO, bh, bw = g.tiles
    n_steps = math.prod(g.grid)
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    p_flt = jnp.dtype(w.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    return (sum(_layout_words(x, w, shape, g, out_dtype))
            + n_steps * bN * g.h_in * g.w_in * g.b_c * p_in
            + n_steps * g.hF * g.wF * g.b_c * b_cO * p_flt
            + g.Np * g.hOp * g.wOp * g.cOp * p_out)


def conv2d_mxu_flops(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W)
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    tiles: Optional[Sequence[int]] = None,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
) -> int:
    """MXU FLOPs one ``conv2d`` dispatch executes: every grid step runs a
    (bh * bw * bN, b_c) @ (b_c, b_cO) GEMM per tap, padding included. The
    conv's own FLOPs over this is the share of the MXU's work that is
    useful."""
    _, g, _ = _geometry(x, w, stride, plan=plan, target=target, tiles=tiles)
    bN, _, b_cO, bh, bw = g.tiles
    return (2 * math.prod(g.grid) * bh * bw * bN * g.hF * g.wF * g.b_c
            * b_cO)
