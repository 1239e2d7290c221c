"""The paged decode kernel (``kernels/flash_attention.paged_decode_attention``)
in interpret mode: one grid step per batch row that fetches only the row's
live pages, ``ceil(len / block_size)`` of them, and folds them in table
order.

Each case is held to two references: a per-page online-softmax fold written
here in plain JAX, which the kernel must match bit for bit (the kernel's
arithmetic is this fold), and the XLA gather entry, to a float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import NEG_INF, paged_decode_attention
from repro.ops.registry import xla_attention_decode

BS = 16


@jax.jit
def _fold_page(q, k, v, m, l, acc, i, length):
    """One page folded into one kv head's online softmax."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = i * k.shape[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def fold_reference(q, kp, vp, tables, lengths):
    """Each (row, kv head) folds its live pages, in table order."""
    B, H, _, hd = q.shape
    KV, bs = kp.shape[1], kp.shape[2]
    g, w = H // KV, tables.shape[1]
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    out = []
    for b in range(B):
        n = min(-(-int(lengths[b]) // bs), w)
        for h in range(KV):
            qh = q[b, h * g:(h + 1) * g, 0].astype(jnp.float32)
            m = jnp.full((g, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((g, 1), jnp.float32)
            acc = jnp.zeros((g, hd), jnp.float32)
            for i in range(n):
                page = int(tables[b, i])
                m, l, acc = _fold_page(
                    qh, kp[page, h].astype(jnp.float32),
                    vp[page, h].astype(jnp.float32), m, l, acc,
                    jnp.int32(i), jnp.int32(lengths[b]))
            out.append((acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype))
    return jnp.stack(out).reshape(B, H, 1, hd)


def _case(name):
    """(q, k pool, v pool, tables, lengths) of one named case. Block 0 is
    the pool's reserved garbage block; table slots past a row's pages
    point at it."""
    B, KV, g, hd, nb, w, dtype = 6, 2, 4, 32, 40, 4, jnp.float32
    if name == "qwen_b64":
        B, KV, g, hd, nb, w, dtype = 64, 2, 8, 128, 200, 3, jnp.bfloat16
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(kq, (B, KV * g, 1, hd), dtype)
    kp = jax.random.normal(kk, (nb, KV, BS, hd), dtype)
    vp = jax.random.normal(kv_, (nb, KV, BS, hd), dtype)
    rs = np.random.RandomState(len(name))
    tables = rs.permutation(np.arange(1, nb))[:B * w].reshape(B, w)
    if name == "lengths":  # page edges, and the whole table
        lengths = [1, 15, 16, 17, w * BS, 2 * BS + 5]
    elif name == "empty_row":
        lengths = [0, 17, 0, 1, 40, 64]
    elif name == "shared_prefix":  # rows 1 and 4 share their first 2 pages
        tables[4, :2] = tables[1, :2]
        lengths = [20, 50, 3, 33, 45, 64]
    elif name == "nan_past_length":
        kp = kp.at[0].set(jnp.nan)
        vp = vp.at[0].set(jnp.nan)
        lengths = [1, 16, 17, 33, 49, 7]
        for b, n in enumerate(lengths):
            tables[b, -(-n // BS):] = 0
    else:  # qwen_b64: most rows free at length 1, a few deep ones
        lengths = [1] * B
        for b, n in zip(range(0, B, 7), [48, 17, 33, 2, 16, 40, 5, 31, 47, 9]):
            lengths[b] = n
    return (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("name", ["lengths", "empty_row", "shared_prefix",
                                  "nan_past_length", "qwen_b64"])
def test_paged_decode_kernel(name):
    q, kp, vp, tables, lengths = _case(name)
    got = np.asarray(paged_decode_attention(q, kp, vp, tables, lengths))
    want = np.asarray(fold_reference(q, kp, vp, tables, lengths))
    np.testing.assert_array_equal(got, want)

    live = np.asarray(lengths) > 0
    assert np.all(got[~live] == 0)  # a row of length 0 stores zeros
    # the gather reference reads every table slot: give it finite garbage
    clean = [jnp.where(jnp.isnan(p), 0, p) for p in (kp, vp)]
    ref = np.asarray(xla_attention_decode(q, *clean, tables, lengths))
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[live].astype(np.float32),
                               ref[live].astype(np.float32),
                               rtol=tol, atol=tol)
