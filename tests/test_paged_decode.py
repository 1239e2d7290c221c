"""The paged decode step (``kernels/flash_attention.paged_decode_attention``
and the ``attention_decode`` op entries) in interpret mode: one grid step per
batch row that fetches only the row's live pages, ``ceil(len / block_size)``
of them, folds them in table order, and writes the step's new k/v row into
the stacked pools of every layer in place, at the row's position
``len - 1`` of one layer.

Each case is held to the step as it was before the pools were written in
place: scatter the new rows into the layer's pool, then attend. The kernel
must match, bit for bit, a per-page online-softmax fold over the scattered
pool written here in plain JAX (the kernel's arithmetic is this fold); both
op entries must match the gather reference over it to a float tolerance.
Every pool position the step did not write stays bit-identical.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.kernels.flash_attention import NEG_INF, paged_decode_attention
from repro.ops.registry import _gather_attend

BS = 16
LAYERS = 3


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
    """Each (row, kv head) folds its live pages of one layer's pools, in
    table order."""
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


def scatter_reference(pool, tables, lengths, layer, rows):
    """The step's write as it was before the in-place kernel: each row of
    positive length puts its (KV, hd) row, zero-padded to the pool's heads,
    at position ``len - 1`` of ``layer``. Returns the pool and a mask of
    the positions written."""
    pool = np.array(pool)
    written = np.zeros(pool.shape[:4], bool)  # (layers, nb, KV, bs)
    rows = np.asarray(rows.astype(pool.dtype))
    for b, n in enumerate(np.asarray(lengths)):
        if n > 0:
            blk, off = int(tables[b, (n - 1) // BS]), (n - 1) % BS
            pool[layer, blk, :, off] = 0
            pool[layer, blk, :, off, :rows.shape[-1]] = rows[b]
            written[layer, blk, :, off] = True
    return pool, written


def _case(name):
    """(q, k pools, v pools, tables, lengths, layer, k rows, v rows) of one
    named case, the pools stacked over ``LAYERS`` layers. Block 0 is the
    pool's reserved garbage block; table slots past a row's pages point at
    it, and a free row's whole table does (at length 1, as the engine
    serves it)."""
    B, KV, g, hd, hp, nb, w, dtype = 6, 2, 4, 32, 32, 40, 4, jnp.float32
    layer = 1
    if name == "qwen_b64":
        B, KV, g, hd, hp, nb, w, dtype = 64, 2, 8, 128, 128, 200, 3, \
            jnp.bfloat16
    elif name == "kv8_lane_padded":  # LFM2's heads: 8 of 64 in 128 lanes
        KV, g, hd, hp, dtype = 8, 4, 64, 128, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 5)
    q = jax.random.normal(ks[0], (B, KV * g, 1, hd), dtype)
    kp = jax.random.normal(ks[1], (LAYERS, nb, KV, BS, hp), dtype)
    vp = jax.random.normal(ks[2], (LAYERS, nb, KV, BS, hp), dtype)
    k_new = jax.random.normal(ks[3], (B, KV, hd), dtype)
    v_new = jax.random.normal(ks[4], (B, KV, hd), dtype)
    if hp > hd:  # a lane-padded pool's heads are zeros past hd
        kp, vp = (p.at[..., hd:].set(0) for p in (kp, vp))
    rs = np.random.RandomState(len(name))
    tables = rs.permutation(np.arange(1, nb))[:B * w].reshape(B, w)
    if name == "lengths":  # page edges (offsets 0 and 15), the whole table
        lengths = [1, 15, 16, 17, w * BS, 2 * BS + 5]
    elif name == "empty_row":
        lengths = [0, 17, 0, 1, 40, 64]
    elif name == "shared_prefix":  # rows 1 and 4 share their first 2 pages
        tables[4, :2] = tables[1, :2]
        lengths = [20, 50, 3, 33, 45, 64]
    elif name == "nan_past_length":
        kp = kp.at[:, 0].set(jnp.nan)
        vp = vp.at[:, 0].set(jnp.nan)
        lengths = [1, 16, 17, 33, 49, 7]
        for b, n in enumerate(lengths):
            tables[b, -(-n // BS):] = 0
        layer = LAYERS - 1
    elif name in ("free_rows", "kv8_lane_padded"):  # rows 1, 3 free
        lengths = [17, 1, 16, 1, 64, 33]
        tables[[1, 3]] = 0
        layer = LAYERS - 1
    else:  # qwen_b64: most rows free at length 1, a few deep ones
        lengths = [1] * B
        for b, n in zip(range(0, B, 7), [48, 17, 33, 2, 16, 40, 5, 31, 47, 9]):
            lengths[b] = n
        layer = 0
    return (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32), layer, k_new, v_new)


def _check_pool(got, before, tables, lengths, layer, rows):
    """Written positions hold the new rows and nothing else moved. Free
    rows all write position 0 of block 0; which of them lands last there
    is the kernel's order, so that slot holds one of their rows."""
    want, written = scatter_reference(before, tables, lengths, layer, rows)
    got = np.asarray(got)
    free = np.asarray(tables)[:, 0] == 0
    if free.any():
        garbage = np.zeros_like(written)
        garbage[layer, 0, :, 0] = True
        hd = rows.shape[-1]
        landed = got[layer, 0, :, 0, :hd]
        assert any(np.array_equal(landed, np.asarray(rows[b].astype(
            got.dtype))) for b in np.flatnonzero(free))
        written &= ~garbage
        got = np.where(garbage[..., None], want, got)
    np.testing.assert_array_equal(got[~written], want[~written])
    np.testing.assert_array_equal(got[written], want[written])
    return want


KERNEL_CASES = ["lengths", "empty_row", "shared_prefix", "nan_past_length",
                "qwen_b64", "free_rows"]


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_paged_decode_kernel(name):
    q, kp, vp, tables, lengths, layer, k_new, v_new = _case(name)
    got, kp2, vp2 = paged_decode_attention(q, kp, vp, tables, lengths, layer,
                                           k_new, v_new)
    kref = _check_pool(kp2, kp, tables, lengths, layer, k_new)
    vref = _check_pool(vp2, vp, tables, lengths, layer, v_new)

    live = np.asarray(tables)[:, 0] != 0  # free rows attend to garbage
    got = np.asarray(got)
    want = np.asarray(fold_reference(q, kref[layer], vref[layer], tables,
                                     lengths))
    np.testing.assert_array_equal(got[live], want[live])
    empty = np.asarray(lengths) == 0
    assert np.all(got[empty] == 0)  # a row of length 0 stores zeros
    # the gather reference reads every table slot: give it finite garbage
    clean = [np.where(np.isnan(p[layer]), 0, p[layer]) for p in (kref, vref)]
    ref = np.asarray(_gather_attend(q, *clean, tables, lengths))
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
    live &= ~empty
    np.testing.assert_allclose(got[live].astype(np.float32),
                               ref[live].astype(np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("name", ["lengths", "free_rows", "kv8_lane_padded",
                                  "qwen_b64"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_paged_decode_entry_matches_scatter_then_attend(backend, name):
    """Both entries of ``ops.attention_decode`` against the step before the
    in-place write: scatter into the layer's pool, then attend. A
    lane-padded pool takes q and the rows at the model's head dim."""
    q, kp, vp, tables, lengths, layer, k_new, v_new = _case(name)
    ctx = ops.ExecutionContext(backend=backend)
    got, kp2, vp2 = ops.attention_decode(q, kp, vp, tables, lengths,
                                         jnp.int32(layer), k_new, v_new,
                                         ctx=ctx)
    assert got.shape == q.shape and kp2.shape == kp.shape
    kref = _check_pool(kp2, kp, tables, lengths, layer, k_new)
    vref = _check_pool(vp2, vp, tables, lengths, layer, v_new)
    live = np.asarray(tables)[:, 0] != 0
    want = np.asarray(_gather_attend(q, kref[layer], vref[layer], tables,
                                     lengths))
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got)[live].astype(np.float32),
                               want[live].astype(np.float32),
                               rtol=tol, atol=tol)


def test_paged_decode_step_in_a_scan_carries_every_layer():
    """The pools ride a scan's carry, one layer per iteration, under a
    donating jit: each layer's slice takes its own rows, as a per-layer
    scatter would."""
    q, kp, vp, tables, lengths, _, k_new, v_new = _case("lengths")
    rows = jnp.stack([k_new * (i + 1) for i in range(LAYERS)])

    @jax.jit
    def step(kp, vp):
        def body(pools, xs):
            layer, r = xs
            o, kp, vp = ops.attention_decode(
                q, *pools, tables, lengths, layer, r, r,
                ctx=ops.ExecutionContext(backend="pallas"))
            return (kp, vp), o
        return jax.lax.scan(body, (kp, vp),
                            (jnp.arange(LAYERS, dtype=jnp.int32), rows))

    (kp2, _), outs = jax.jit(step, donate_argnums=(0, 1))(kp + 0, vp + 0)
    want = np.array(kp)
    for layer in range(LAYERS):
        want = scatter_reference(want, tables, lengths, layer,
                                 rows[layer])[0]
    np.testing.assert_array_equal(np.asarray(kp2), want)
    assert outs.shape == (LAYERS,) + q.shape
