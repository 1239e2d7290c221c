"""Plain float32 convolution, the yardstick of the conv cells, and its control.

``lax.conv_general_dilated`` in float32 at ``Precision.HIGHEST``, VALID,
NCHW input and OIHW filter, walked in batch chunks so that it never holds
a second full output. It imports nothing of the program under test.

The control rounds both operands through float8 e4m3 with a per-tensor
scale, the precision step below the configuration's bfloat16, and is
otherwise the same convolution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# e4m3 as ``lax.reduce_precision`` rounds it (IEEE-style: the top exponent
# is reserved, so 240 is the largest finite value). An explicit rounding op,
# unlike a round trip through a float8 dtype, which XLA on the TPU may drop
# as excess precision.
FP8_MAX = 240.0


def fp8_round(a: jax.Array) -> jax.Array:
    a = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def conv_f32(x, w, stride: int):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (stride, stride),
        "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def _in_chunks(a, n_chunks):
    return a.reshape(n_chunks, a.shape[0] // n_chunks, *a.shape[1:])


def _chunks(n: int) -> int:
    return next(c for c in (20, 10, 8, 5, 4, 2, 1) if n % c == 0)


@functools.partial(jax.jit, static_argnames=("stride",))
def error(out, x, w, *, stride: int):
    """(max |out - ref|, max |ref|) of ``out`` against the reference."""
    c = _chunks(x.shape[0])

    def one(args):
        xc, oc = args
        ref = conv_f32(xc, w, stride)
        return (jnp.max(jnp.abs(oc.astype(jnp.float32) - ref)),
                jnp.max(jnp.abs(ref)))

    err, mag = jax.lax.map(one, (_in_chunks(x, c), _in_chunks(out, c)))
    return jnp.max(err), jnp.max(mag)


@functools.partial(jax.jit, static_argnames=("stride",))
def control_error(x, w, *, stride: int):
    """What :func:`error` reads when the fp8 control's output takes the
    place of the program's."""
    c = _chunks(x.shape[0])
    xq, wq = fp8_round(x), fp8_round(w)

    def one(args):
        xc, xqc = args
        ref = conv_f32(xc, w, stride)
        return (jnp.max(jnp.abs(conv_f32(xqc, wq, stride) - ref)),
                jnp.max(jnp.abs(ref)))

    err, mag = jax.lax.map(one, (_in_chunks(x, c), _in_chunks(xq, c)))
    return jnp.max(err), jnp.max(mag)
