"""Executable distributed conv2d: halo exchange vs. all-gather, under
``shard_map`` on a real device mesh.

Two lowering strategies for the same 7NL conv, both driven by one
:class:`~repro.distributed.geometry.DistConvGeometry`:

  * :func:`halo_conv` — the paper-§4.2 blocking made runnable. Inputs are
    sharded over ``(N, cI, hO, wO)`` as disjoint owned slabs; each device
    ``ppermute``-fetches the ``h_F - sh`` overlap rows (and ``w_F - sw``
    cols) from its spatial neighbor, runs the shard-local conv through the
    ``repro.ops`` registry (so the PR-4 LP-tiled Pallas kernel serves each
    shard), and ``psum``s cI-partial outputs.
  * :func:`allgather_conv` — the naive baseline: every device all-gathers
    the full input (and the filter along cI), then computes only its own
    output block. Same sharded inputs, same outputs, (P-1)/P x |I| more
    wire traffic.

Both return the exact global VALID conv (bitwise vs. the single-device
reference when cI is not split; cI splits reassociate the reduction).
The shard-local conv dispatches at trace time, so the whole thing jits.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.parallel_tiling import ParallelBlocking, optimize_parallel_blocking

from .geometry import DIST_AXES, DistConvGeometry


def default_blocking(x_shape, w_shape, stride: Tuple[int, int],
                     P_devices: Optional[int] = None,
                     prec=None) -> ParallelBlocking:
    """The LP-chosen processor grid for this conv over ``P_devices`` devices,
    restricted to the axes the distributed lowering serves."""
    from repro.core.conv_model import ConvShape, Precision

    N, c_I, H, W = x_shape
    c_O, _, h_F, w_F = w_shape
    sh, sw = stride
    shape = ConvShape(N=N, c_I=c_I, c_O=c_O,
                      w_O=(W - w_F) // sw + 1, h_O=(H - h_F) // sh + 1,
                      w_F=w_F, h_F=h_F, sw=sw, sh=sh,
                      prec=prec or Precision())
    P_devices = P_devices or len(jax.devices())
    return optimize_parallel_blocking(shape, P_devices,
                                      restrict_axes=DIST_AXES)


def _geometry(x, w, stride, blocking) -> DistConvGeometry:
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    return DistConvGeometry.build(
        N=N, c_I=c_I, c_O=c_O,
        h_O=(H - h_F) // sh + 1, w_O=(W - w_F) // sw + 1,
        h_F=h_F, w_F=w_F, sh=sh, sw=sw, grid=blocking).validate()


def _check_mesh(mesh: Mesh, geom: DistConvGeometry) -> Mesh:
    names = tuple(mesh.axis_names)
    if names != DIST_AXES:
        raise ValueError(f"distributed conv needs mesh axes {DIST_AXES}, "
                         f"got {names} (use launch.make_conv_mesh)")
    sizes = tuple(mesh.devices.shape)
    if sizes != geom.grid:
        raise ValueError(f"mesh sizes {sizes} do not match the blocking grid "
                         f"{geom.grid}")
    return mesh


def _resolve_mesh(mesh: Optional[Mesh], geom: DistConvGeometry) -> Mesh:
    if mesh is not None:
        return _check_mesh(mesh, geom)
    from repro.launch.mesh import make_conv_mesh

    return make_conv_mesh(geom.grid_dict())


def _local_ctx(ctx, backend: str):
    """The shard-local execution context: same target, mesh stripped (each
    shard is a single device), the requested local backend pinned."""
    target = dataclasses.replace(ctx.target, mesh_axes=())
    return dataclasses.replace(ctx, target=target, backend=backend)


def _pad_operands(x, w, geom: DistConvGeometry):
    """Pad to the sharded global dims. Input rows/cols beyond the tight
    VALID extent are never consumed by a real output; padded cI channels
    contribute zeros; padded N rows are sliced away."""
    N, c_I, H, W = x.shape
    c_O = w.shape[0]
    x = x[:, :, :min(H, geom.Hp), :min(W, geom.Wp)]
    x = jnp.pad(x, ((0, geom.Np - N), (0, geom.cIp - c_I),
                    (0, geom.Hp - x.shape[2]), (0, geom.Wp - x.shape[3])))
    w = jnp.pad(w, ((0, 0), (0, geom.cIp - c_I), (0, 0), (0, 0)))
    return x, w, c_O


def _shift_from_next(block, axis_name: str, size: int):
    """Each device receives ``block`` from its successor along ``axis_name``
    (ring: the last device receives the first's — wraparound data only ever
    feeds padded output rows, see geometry.py)."""
    return jax.lax.ppermute(
        block, axis_name, [(j, (j - 1) % size) for j in range(size)])


def halo_conv(x, w, stride=(1, 1), blocking=None, mesh: Optional[Mesh] = None,
              ctx=None, local_backend: str = "pallas", out_dtype=jnp.float32,
              full_output: bool = False):
    """Distributed halo-exchange conv2d (NCHW x OIHW, VALID padding).

    ``blocking`` is a :class:`ParallelBlocking` (or axis->procs dict) whose
    grid must match ``mesh`` (built via ``launch.make_conv_mesh`` when
    omitted). The shard-local conv dispatches through ``repro.ops`` with
    ``local_backend``, so pallas shards run the PR-4 LP-tiled kernel.

    ``full_output=True`` returns the padded, still-sharded global output
    ``(Np, c_O, hOp, wOp)``: slicing padded spatial dims back to
    ``(h_O, w_O)`` across even shards makes XLA insert small re-layout
    permutes, so pipelines that feed another sharded op should keep the
    padded form and slice once at the end. The measured-words counter
    charges only the algorithm's halo + psum traffic, never this fixup."""
    from repro import ops

    ctx = ops.default_context() if ctx is None else ctx
    if blocking is None:
        blocking = default_blocking(x.shape, w.shape, stride)
    geom = _geometry(x, w, stride, blocking)
    mesh = _resolve_mesh(mesh, geom)
    lctx = _local_ctx(ctx, local_backend)
    gN, gcI, ghO, gwO = geom.grid
    N, c_I = x.shape[0], x.shape[1]
    xp, wp, c_O = _pad_operands(x, w, geom)
    sh, sw = geom.sh, geom.sw

    def body(xl, wl):
        # xl: (bN, b_cI, bh*sh, bw*sw)  wl: (c_O, b_cI, h_F, w_F)
        # Rows first, then columns over the row-extended height, so corner
        # halos ride the second exchange. Single-shard axes skip the wire
        # entirely: their windows are completed with a *local* zero fill
        # below (those rows/cols only ever feed padded outputs), keeping the
        # ppermute traffic equal to geometry.halo_words for every grid.
        if geom.halo_h > 0 and ghO > 1:
            top = jax.lax.slice_in_dim(xl, 0, geom.halo_h, axis=2)
            xl = jnp.concatenate([xl, _shift_from_next(top, "hO", ghO)],
                                 axis=2)
        if geom.halo_w > 0 and gwO > 1:
            left = jax.lax.slice_in_dim(xl, 0, geom.halo_w, axis=3)
            xl = jnp.concatenate([xl, _shift_from_next(left, "wO", gwO)],
                                 axis=3)
        pad_h = max(geom.h_ext - xl.shape[2], 0)
        pad_w = max(geom.w_ext - xl.shape[3], 0)
        if pad_h or pad_w:
            xl = jnp.pad(xl, ((0, 0), (0, 0), (0, pad_h), (0, pad_w)))
        xl = xl[:, :, :geom.h_ext, :geom.w_ext]
        # every shard must now hold an exact halo window (kernel contract)
        from repro.kernels.conv2d import exact_window

        assert exact_window(geom.h_ext, geom.w_ext, geom.h_F, geom.w_F,
                            sh, sw), "mis-built halo window"
        # shard-local conv through the registry: f32 partials for the psum
        ol = ops.conv2d(xl, wl, stride=(sh, sw), ctx=lctx,
                        out_dtype=jnp.float32)
        if gcI > 1:
            ol = jax.lax.psum(ol, "cI")
        return ol.astype(out_dtype)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("N", "cI", "hO", "wO"), P(None, "cI", None, None)),
        out_specs=P("N", None, "hO", "wO"), check_vma=False)
    out = f(xp, wp)
    if full_output:
        return out
    return out[:N, :c_O, :geom.h_O, :geom.w_O]


def allgather_conv(x, w, stride=(1, 1), blocking=None,
                   mesh: Optional[Mesh] = None, ctx=None,
                   local_backend: str = "pallas", out_dtype=jnp.float32,
                   full_output: bool = False):
    """The naive all-gather baseline: same sharded inputs as
    :func:`halo_conv`, but every device gathers the *full* input (and the
    filter along cI) before computing its own output block."""
    from repro import ops

    ctx = ops.default_context() if ctx is None else ctx
    if blocking is None:
        blocking = default_blocking(x.shape, w.shape, stride)
    geom = _geometry(x, w, stride, blocking)
    mesh = _resolve_mesh(mesh, geom)
    lctx = _local_ctx(ctx, local_backend)
    gN, gcI, ghO, gwO = geom.grid
    N, c_I = x.shape[0], x.shape[1]
    xp, wp, c_O = _pad_operands(x, w, geom)
    sh, sw = geom.sh, geom.sw

    def body(xl, wl):
        xg = xl
        for name, size, arr_axis in (("N", gN, 0), ("cI", gcI, 1),
                                     ("hO", ghO, 2), ("wO", gwO, 3)):
            if size > 1:
                xg = jax.lax.all_gather(xg, name, axis=arr_axis, tiled=True)
        wg = (jax.lax.all_gather(wl, "cI", axis=1, tiled=True)
              if gcI > 1 else wl)
        # tail windows read past the owned extent: zero-pad locally (free)
        xg = jnp.pad(xg, ((0, 0), (0, 0), (0, geom.halo_h),
                          (0, geom.halo_w)))
        i_n = jax.lax.axis_index("N")
        i_h = jax.lax.axis_index("hO")
        i_w = jax.lax.axis_index("wO")
        win = jax.lax.dynamic_slice(
            xg, (i_n * geom.bN, 0, i_h * geom.bh * sh, i_w * geom.bw * sw),
            (geom.bN, geom.cIp, geom.h_ext, geom.w_ext))
        ol = ops.conv2d(win, wg, stride=(sh, sw), ctx=lctx,
                        out_dtype=jnp.float32)
        return ol.astype(out_dtype)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("N", "cI", "hO", "wO"), P(None, "cI", None, None)),
        out_specs=P("N", None, "hO", "wO"), check_vma=False)
    out = f(xp, wp)
    if full_output:
        return out
    return out[:N, :c_O, :geom.h_O, :geom.w_O]


# ---------------------------------------------------------------------------
# Measured inter-device word counters (shape-only; ShapeDtypeStruct works).
# ---------------------------------------------------------------------------

def _word_widths(x, w, out_dtype):
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    p_flt = jnp.dtype(w.dtype).itemsize / 4.0
    p_out = jnp.dtype(out_dtype).itemsize / 4.0
    return p_in, p_flt, p_out


def conv2d_dist_comm_words(x, w, stride=(1, 1), blocking=None,
                           out_dtype=jnp.float32, **_kw) -> float:
    """Measured inter-device words (32-bit, per device) one ``halo_conv``
    dispatch moves: halo ``ppermute`` volume + cI ``psum`` volume, computed
    from the same :class:`DistConvGeometry` the execution lowers.

    The psum leg always charges f32 words whatever ``out_dtype`` is: the
    shard-local conv emits f32 partials (the paper's accumulate-in-f32
    discipline) and the reduction runs *before* the ``astype``, so that is
    what the all-reduce puts on the wire."""
    if blocking is None:
        blocking = default_blocking(x.shape, w.shape, stride)
    geom = _geometry(x, w, stride, blocking)
    p_in, _, _ = _word_widths(x, w, out_dtype)
    return geom.comm_words(p_in=p_in, p_out=1.0)


def allgather_comm_words(x, w, stride=(1, 1), blocking=None,
                         out_dtype=jnp.float32, **_kw) -> float:
    """Per-device words the all-gather baseline moves for the same grid."""
    if blocking is None:
        blocking = default_blocking(x.shape, w.shape, stride)
    geom = _geometry(x, w, stride, blocking)
    p_in, p_flt, _ = _word_widths(x, w, out_dtype)
    return geom.allgather_words(p_in=p_in, p_flt=p_flt)
