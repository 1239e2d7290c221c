"""Im2Col baseline convolution: materialized patches -> LP-tiled Pallas GEMM.

The algorithm the paper's §5 tiling is measured against (Figs 2-4): lower the
7NL convolution to one big GEMM by materializing the patch matrix

    P[(n, ho, wo), (ci, hf, wf)] = Input[n, ci, ho*sh + hf, wo*sw + wf]

of shape (N*h_O*w_O, c_I*h_F*w_F) — every input element is copied up to
h_F*w_F times — then computing P @ Filter.T with the LP-tiled Pallas matmul.
The patch expansion is plain XLA (its cost is pure data movement, which is
exactly what the baseline is supposed to pay); the GEMM is the same
double-buffered Pallas kernel the direct path uses for its taps, so the
comparison isolates the *algorithm's* communication, not kernel quality.

``im2col_hbm_words`` counts the words the baseline moves: read the input,
write the expanded patch matrix, the GEMM's measured stream/store words, and
the pass that turns the GEMM's (pixel, c_O) rows back into NCHW — the number
the direct kernel's halo tiling is supposed to beat (the paper's 13-150%
Im2Col-vs-tiled gap), counted as the direct kernel counts its own layout
passes.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.plan import ExecutionPlan, HardwareTarget

from .matmul import matmul, matmul_access_plan, matmul_hbm_words


def im2col_patches(x: jax.Array, h_F: int, w_F: int,
                   stride: Tuple[int, int]) -> jax.Array:
    """(N, c_I, H, W) -> (N*h_O*w_O, c_I*h_F*w_F) patch matrix whose column
    order (ci, hf, wf) matches ``filter.reshape(c_O, -1)``."""
    N, c_I, H, W = x.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    taps = [
        jax.lax.slice(
            x, (0, 0, hf, wf),
            (N, c_I, hf + (h_O - 1) * sh + 1, wf + (w_O - 1) * sw + 1),
            (1, 1, sh, sw))  # (N, c_I, h_O, w_O)
        for hf in range(h_F) for wf in range(w_F)
    ]
    p = jnp.stack(taps, axis=2)  # (N, c_I, h_F*w_F, h_O, w_O)
    p = p.transpose(0, 3, 4, 1, 2)  # (N, h_O, w_O, c_I, h_F*w_F)
    return p.reshape(N * h_O * w_O, c_I * h_F * w_F)


def conv2d_im2col(
    x: jax.Array,  # (N, c_I, H, W)
    w: jax.Array,  # (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    out_dtype=jnp.float32,
    ctx=None,
    target: Optional[HardwareTarget] = None,
) -> jax.Array:
    """Im2Col convolution (VALID padding): patches -> LP-tiled Pallas GEMM.
    Execution policy rides ``ctx``; ``target=`` is legacy (DeprecationWarning;
    lint VRF015)."""
    from repro.plan import warn_legacy_kernel_kwargs

    warn_legacy_kernel_kwargs("conv2d_im2col", target=target)
    if ctx is None and target is not None:
        # absorb the legacy kwarg so the inner matmul doesn't re-warn
        from types import SimpleNamespace
        ctx = SimpleNamespace(target=target, autotune=None)
    N, c_I, H, W = x.shape
    c_O, c_I2, h_F, w_F = w.shape
    assert c_I == c_I2
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    patches = im2col_patches(x, h_F, w_F, stride)
    wmat = w.reshape(c_O, c_I * h_F * w_F).T  # (k, c_O)
    out = matmul(patches, wmat, out_dtype=out_dtype,
                 ctx=ctx)  # (N*h_O*w_O, c_O)
    return out.reshape(N, h_O, w_O, c_O).transpose(0, 3, 1, 2)


def im2col_hbm_words(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W)
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
) -> float:
    """Measured HBM words (32-bit) one ``conv2d_im2col`` dispatch moves:
    patch expansion (read input + write the expanded matrix, as in the
    paper's im2col volume model), the Pallas GEMM's measured words for the
    launch geometry its plan resolves, and the NCHW output pass (read and
    write the (m, c_O) result). Shapes/dtypes only."""
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    m, k = N * h_O * w_O, c_I * h_F * w_F
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    expand = p_in * (N * c_I * H * W) + p_in * m * k
    gemm = matmul_hbm_words(
        jax.ShapeDtypeStruct((m, k), x.dtype),
        jax.ShapeDtypeStruct((k, c_O), w.dtype),
        target=target, out_dtype=out_dtype)
    return expand + gemm + _output_layout_words(m, c_O, out_dtype)


def _output_layout_words(m: int, c_O: int, out_dtype) -> float:
    """Words of the pass that reorders the GEMM's (m, c_O) rows to NCHW."""
    return 2.0 * jnp.dtype(out_dtype).itemsize / 4.0 * m * c_O


def im2col_access_plan(
    x,  # array or ShapeDtypeStruct, (N, c_I, H, W)
    w,  # array or ShapeDtypeStruct, (c_O, c_I, h_F, w_F)
    stride: Tuple[int, int] = (1, 1),
    target: Optional[HardwareTarget] = None,
    out_dtype=jnp.float32,
):
    """The :class:`repro.verify.access.KernelAccessPlan` of one
    ``conv2d_im2col`` dispatch: the GEMM's access plan (same grid, same A/B
    windows over the patch matrix) between the XLA passes as flat traffic —
    read the input once, write the (m, k) patch matrix once, and reorder the
    (m, c_O) result to NCHW — exactly what ``im2col_hbm_words`` charges."""
    import dataclasses as _dc

    from repro.verify.access import FlatAccess

    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    h_O = (H - h_F) // sh + 1
    w_O = (W - w_F) // sw + 1
    m, k = N * h_O * w_O, c_I * h_F * w_F
    p_in = jnp.dtype(x.dtype).itemsize / 4.0
    gemm = matmul_access_plan(
        jax.ShapeDtypeStruct((m, k), x.dtype),
        jax.ShapeDtypeStruct((k, c_O), w.dtype),
        target=target, out_dtype=out_dtype, op="conv2d[im2col]")
    expand = (
        FlatAccess(name="im2col_input_read", kind="load",
                   words=p_in * N * c_I * H * W,
                   note="XLA patch expansion reads the input once"),
        FlatAccess(name="im2col_patch_write", kind="store",
                   words=p_in * float(m) * k,
                   note="XLA patch expansion writes the (m, k) matrix"),
    )
    layout = FlatAccess(name="im2col_output_layout", kind="store",
                        words=_output_layout_words(m, c_O, out_dtype),
                        note="XLA reorders the (m, c_O) result to NCHW")
    return _dc.replace(gemm, accesses=expand + gemm.accesses + (layout,),
                       note="patch expansion + LP-tiled GEMM + NCHW pass")
