"""Tests for ``repro.verify``: the static communication auditor, the DMA
hazard simulator, the AST/registry lint, the seeded mutants, and the
``explain(audit=True)`` / plan-construction integrations."""

import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import ops
from repro.plan import ConvSpec, MatmulSpec, Planner, TPU_V5E
from repro.verify import (audit_access_plan, audit_decision,
                          check_schedule, double_buffered_schedule,
                          validate_execution_plan)
from repro.verify.hazards import (DRAIN, DmaEvent, DmaSchedule, READ, START,
                                  STORE, WAIT)
from repro.verify.lint import lint_file, lint_registry, run_lint
from repro.verify.mutants import run_seeded_mutants

PALLAS = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
IM2COL = ops.ExecutionContext(target=TPU_V5E, backend="im2col")


def _assert_exact(decision):
    assert decision.audited is not None
    assert decision.measured_words is not None
    assert decision.audited == pytest.approx(decision.measured_words,
                                             rel=1e-6)


# ---------------------------------------------------------------------------
# audit exactness: the abstract walk reproduces words_fn per registered op
# ---------------------------------------------------------------------------

# a stem whose column taps fold into the lanes: 3 channels x 4 phases x 4
# column taps = 48 of 128 lanes
FOLDED_X = jax.ShapeDtypeStruct((8, 3, 37, 37), jnp.bfloat16)
FOLDED_W = jax.ShapeDtypeStruct((16, 3, 7, 7), jnp.bfloat16)


def test_conv2d_audit_matches_words_fn_both_backends():
    for x, w in ((jax.ShapeDtypeStruct((8, 64, 58, 58), jnp.bfloat16),
                  jax.ShapeDtypeStruct((128, 64, 3, 3), jnp.bfloat16)),
                 (FOLDED_X, FOLDED_W)):
        for ctx in (PALLAS, IM2COL):
            d = ops.explain("conv2d", ctx, spec_args=(x, w),
                            spec_kw={"stride": (2, 2)}, audit=True)
            _assert_exact(d)


def test_folded_conv2d_window_one_column_off_fails_coverage():
    """Folded column j holds space-to-depth columns j .. j + 3, so a tile's
    window needs exactly its own columns: one column to the right moves the
    same words and misses the tile's first column."""
    from repro.kernels.conv2d import _geometry, conv2d_access_plan
    from repro.verify.access import WindowAccess

    g = _geometry(FOLDED_X, FOLDED_W, (2, 2), target=TPU_V5E)[1]
    assert (g.fold, g.wF, g.w_in) == (4, 1, g.tiles[4])
    ap = conv2d_access_plan(FOLDED_X, FOLDED_W, stride=(2, 2),
                            target=TPU_V5E)
    assert audit_access_plan(ap).ok

    def shifted(acc):
        def window(*axes):
            win = list(acc.window(*axes))
            win[1] = (win[1][0] + 1, win[1][1])
            return tuple(win)
        return dataclasses.replace(acc, window=window)

    off = tuple(shifted(a) if isinstance(a, WindowAccess) and a.name == "input"
                else a for a in ap.accesses)
    bad = audit_access_plan(dataclasses.replace(ap, accesses=off))
    assert not bad.ok
    assert any(p.startswith("input: dim 1") and "misses the required" in p
               for p in bad.problems)


def test_matmul_audit_matches_words_fn_including_fit_shrunk_tiles():
    # the tall-skinny im2col GEMM whose lane-snapped bk the planner must
    # shrink back to feasibility (_fit_matmul_tiles) — audit stays exact
    for m, k, n in ((512, 384, 256), (23328, 576, 64)):
        a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
        b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
        d = ops.explain("matmul", PALLAS, spec_args=(a, b), audit=True)
        _assert_exact(d)
        ep = d.plan
        prec = ep.target.precision
        bm, bn, bk = ep.tiles
        fp = bm * bk * prec.p_I + bk * bn * prec.p_F + bm * bn * prec.p_O
        assert fp <= ep.target.memory_model().M_eff


def test_conv1d_audit_matches_words_fn():
    x = jax.ShapeDtypeStruct((2, 33, 130), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 130), jnp.bfloat16)
    d = ops.explain("conv1d_causal", PALLAS, spec_args=(x, w), audit=True)
    _assert_exact(d)


def test_attention_audit_matches_words_fn_single_kv_block_corner():
    # n_k == 1 with n_q > 1: K/V are fetched once, not once per q block —
    # the words_fn corner the auditor originally flagged
    q = jax.ShapeDtypeStruct((2, 8, 512, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8, 128, 64), jnp.bfloat16)
    d = ops.explain("attention", PALLAS, spec_args=(q, kv, kv), audit=True)
    _assert_exact(d)


def test_attention_decode_paged_audit():
    B, KV, BLOCK, hd, nb, w = 4, 2, 16, 128, 64, 4
    d = ops.explain(
        "attention_decode", PALLAS,
        spec_args=(jax.ShapeDtypeStruct((B, 16, 1, hd), jnp.bfloat16),
                   jax.ShapeDtypeStruct((3, nb, KV, BLOCK, hd), jnp.bfloat16),
                   jax.ShapeDtypeStruct((3, nb, KV, BLOCK, hd), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, w), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32),
                   jax.ShapeDtypeStruct((B, KV, hd), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, KV, hd), jnp.bfloat16)),
        audit=True)
    _assert_exact(d)


def test_paged_decode_page_stream_audit():
    """The page stream as manual copies: a concrete table that shares pages
    between rows (a prefix hit) moves the words_fn's words, since copies
    elide no repeat; a copy one block off the table misses the page the
    row needs, and a write-back one block off misses the position the row
    writes."""
    import numpy as np

    from repro.kernels.flash_attention import (paged_decode_access_plan,
                                               paged_decode_hbm_words)

    B, KV, g, w, bs, hd, nb = 3, 2, 4, 3, 16, 32, 12
    tables = np.array([[1, 2, 3], [1, 2, 4], [5, 0, 0]])
    ap = paged_decode_access_plan(B, KV, g, w, bs, hd, nb, layers=2,
                                  tables=tables)
    report = audit_access_plan(ap)
    assert report.ok, report.problems
    assert report.counted_words == paged_decode_hbm_words(B, KV, g, w, bs,
                                                          hd)
    assert report.per_access["k_pool"] == B * w * KV * bs * hd
    assert report.per_access["k_page_back"] == B * KV * bs * hd
    off = [dataclasses.replace(
        a, window=lambda b, i: ((0, 1), (tables[b, i] + 1, 1), (0, KV),
                                (0, bs), (0, hd)))
        if a.name == "k_pool" else a for a in ap.accesses]
    bad = audit_access_plan(dataclasses.replace(ap, accesses=tuple(off)))
    assert any("k_pool" in p and "misses the required" in p
               for p in bad.problems)
    off = [dataclasses.replace(
        a, window=lambda b: ((0, 1), (tables[b, w - 2], 1), (0, KV),
                             (0, bs), (0, hd)))
        if a.name == "v_page_back" else a for a in ap.accesses]
    bad = audit_access_plan(dataclasses.replace(ap, accesses=tuple(off)))
    assert any("v_page_back" in p and "misses the required" in p
               for p in bad.problems)


def test_audit_decision_flags_wrong_measured_words():
    x = jax.ShapeDtypeStruct((2, 8, 12, 12), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 8, 3, 3), jnp.float32)
    d = ops.explain("conv2d", PALLAS, spec_args=(x, w), audit=True)
    entry = ops.get_backend("pallas").ops["conv2d"]
    ap = entry.access_plan_fn(PALLAS, d.plan, x, w)
    bad = dataclasses.replace(d, measured_words=d.measured_words * 2)
    report = audit_decision(ap, bad)
    assert not report.ok
    assert any("!= words_fn" in p for p in report.problems)


# ---------------------------------------------------------------------------
# DMA hazard simulator
# ---------------------------------------------------------------------------

def test_double_buffered_schedule_is_hazard_free():
    for n in (1, 2, 7):
        assert check_schedule(double_buffered_schedule(n)) == []


def test_read_before_wait_is_h1():
    sched = DmaSchedule(n_slots=2, n_steps=1, name="t", events=(
        DmaEvent(START, 0, 0), DmaEvent(READ, 0, 0)))
    assert any(h.code in ("H1", "H4") for h in check_schedule(sched))


def test_double_start_and_overwrite_are_flagged():
    sched = DmaSchedule(n_slots=2, n_steps=2, name="t", events=(
        DmaEvent(START, 0, 0), DmaEvent(START, 0, 0),
        DmaEvent(WAIT, 0, 0), DmaEvent(READ, 0, 0)))
    assert any(h.code == "H2" for h in check_schedule(sched))


def test_dangling_start_is_h5():
    sched = DmaSchedule(n_slots=2, n_steps=1, name="t", events=(
        DmaEvent(START, 1, 0),))
    assert any(h.code == "H5" for h in check_schedule(sched))


WRITE_BACK_FAULTS = {
    # the page copied back out before its own copy in landed
    "store_before_wait": ((START, 0, 0), (STORE, 0, 0), (WAIT, 0, 0),
                          (READ, 0, 0), (DRAIN, 0, 0)),
    # the write-back never drained
    "undrained": ((START, 0, 0), (WAIT, 0, 0), (STORE, 0, 0),
                  (READ, 0, 0)),
    # the slot refilled while its write-back still reads it
    "refill_under_store": ((START, 0, 0), (WAIT, 0, 0), (STORE, 0, 0),
                           (READ, 0, 0), (START, 0, 2), (DRAIN, 0, 0),
                           (WAIT, 0, 2), (READ, 0, 2)),
}


@pytest.mark.parametrize("fault,code", [("store_before_wait", "H6"),
                                        ("undrained", "H5"),
                                        ("refill_under_store", "H7")])
def test_write_back_hazards(fault, code):
    """The paged decode's schedule, page written back after its wait and
    drained before the slot's refill, is clean at any width; each way of
    breaking that order is flagged."""
    for n in (1, 2, 7):
        assert check_schedule(double_buffered_schedule(
            n, write_back=True)) == []
    sched = DmaSchedule(n_slots=2, n_steps=3, name="t", events=tuple(
        DmaEvent(*e) for e in WRITE_BACK_FAULTS[fault]))
    assert any(h.code == code for h in check_schedule(sched))


# ---------------------------------------------------------------------------
# seeded mutants: the auditor's own regression harness
# ---------------------------------------------------------------------------

def test_all_seeded_mutants_are_caught():
    results = run_seeded_mutants()
    assert len(results) == 6
    escaped = [name for name, caught, _ in results if not caught]
    assert not escaped, f"mutants escaped the auditor: {escaped}"


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

def test_lint_flags_kv_repeat_outside_kernels(tmp_path):
    f = tmp_path / "src" / "repro" / "serving" / "bad.py"
    f.parent.mkdir(parents=True)
    f.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def grow(k, groups):
            return jnp.repeat(k, groups, axis=1)
    """))
    codes = [v.code for v in lint_file(f, tmp_path)]
    assert "VRF003" in codes


def test_lint_flags_pallas_call_outside_kernels(tmp_path):
    f = tmp_path / "src" / "repro" / "model" / "bad.py"
    f.parent.mkdir(parents=True)
    f.write_text("import jax.experimental.pallas as pl\n"
                 "y = pl.pallas_call(lambda: None, out_shape=None)\n")
    codes = [v.code for v in lint_file(f, tmp_path)]
    assert "VRF001" in codes


def test_lint_allows_kernels_dir(tmp_path):
    f = tmp_path / "src" / "repro" / "kernels" / "ok.py"
    f.parent.mkdir(parents=True)
    f.write_text("import jax.experimental.pallas as pl\n"
                 "y = pl.pallas_call(lambda: None, out_shape=None)\n")
    assert lint_file(f, tmp_path) == []


def test_registry_lint_and_tree_lint_are_clean():
    assert lint_registry() == []
    assert run_lint() == []


# ---------------------------------------------------------------------------
# plan-construction validation
# ---------------------------------------------------------------------------

def test_validate_execution_plan_accepts_real_plans():
    for spec in (ConvSpec(N=4, c_I=8, c_O=16, w_O=10, h_O=10, w_F=3, h_F=3),
                 MatmulSpec(512, 384, 256)):
        assert validate_execution_plan(Planner(TPU_V5E).plan(spec)) == []


def test_validate_execution_plan_rejects_uncovering_grid():
    ep = Planner(TPU_V5E).plan(MatmulSpec(512, 384, 256))
    bad = dataclasses.replace(ep, grid=(1, 1, 1), tiles=(8, 8, 8))
    problems = validate_execution_plan(bad)
    assert any("does not cover" in p for p in problems)


def test_access_plan_dma_schedules_simulate_clean():
    a = jax.ShapeDtypeStruct((512, 384), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((384, 256), jnp.bfloat16)
    d = ops.explain("matmul", PALLAS, spec_args=(a, b), audit=True)
    entry = ops.get_backend("pallas").ops["matmul"]
    ap = entry.access_plan_fn(PALLAS, d.plan, a, b)
    report = audit_access_plan(ap)
    assert report.ok and report.hazards == []
