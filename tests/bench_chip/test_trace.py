"""The trace reduction, on events worked out by hand."""

import os

import pytest

from benchmarks.chip import trace as tr


def E(name, start, end, plane="/device:TPU:0", line="XLA Ops", cc=False):
    return tr.Event(name, start, end, plane, line, cc)


def test_union_and_gaps_by_hand():
    ivs = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert tr.union_ns(ivs) == 15 + 11
    assert tr.gaps_ns(ivs, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert tr.gaps_ns(ivs, 2, 12) == []
    assert tr.union_ns([]) == 0


def test_synthetic_trace_by_hand():
    """Window [100, 200) ns. Device ops: a custom call over [90, 130)
    (clipped to [100, 130): 30 ns), an XLA op [120, 155) (35 ns, 10 of them
    overlapping the kernel), and an XLA op [170, 180) (10 ns) inside a while
    loop [168, 182). Busy is the union of all of them: [100, 155) and
    [168, 182), 69 ns. Op time counts the innermost ops alone (the loop
    encloses fusion.3): 30 + 35 + 10 = 75 ns. Idle 31 of 100 ns, in two gaps:
    [182, 200) under ``bench/job`` alone, and [155, 168) under the host's
    ``bench/job > PjitFunction(decode)`` (the other thread's event does not
    count: it is not the span's thread)."""
    ops = [E("k.1 custom-call(", 90, 130, cc=True), E("fusion.2", 120, 155),
           E("while.9", 168, 182), E("fusion.3", 170, 180)]
    mods = [E("jit_decode(77)", 90, 150, line="XLA Modules"),
            E("jit_prefill(5)", 170, 180, line="XLA Modules"),
            E("jit_decode_other(1)", 185, 190, line="XLA Modules")]
    host = [E("bench/window", 100, 200, "/host:CPU", "python"),
            E("bench/job", 101, 200, "/host:CPU", "python"),
            E("PjitFunction(decode)", 140, 172, "/host:CPU", "python"),
            E("elsewhere", 150, 170, "/host:CPU", "other thread")]
    t = tr.Trace(ops, mods, host, (100, 200))
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(69e-9)
    assert t.op_seconds() == pytest.approx(75e-9)
    assert t.op_seconds(custom_call=True) == pytest.approx(30e-9)
    assert t.op_seconds(custom_call=False) == pytest.approx(45e-9)
    assert t.module_seconds("jit_decode") == [pytest.approx(60e-9)]
    assert t.module_seconds("jit_prefill") == [pytest.approx(10e-9)]
    assert t.top_ops(2) == [["fusion.2", pytest.approx(35e-9)],
                            ["k.1 custom-call(", pytest.approx(30e-9)]]
    assert t.idle_gaps(5) == [
        ["bench/job", pytest.approx(18e-9)],
        ["bench/job > PjitFunction(decode)", pytest.approx(13e-9)]]


def test_leaves_drop_enclosing_events():
    outer, inner, after = E("while", 0, 10), E("f", 2, 5), E("g", 10, 12)
    assert tr.leaves([after, inner, outer]) == [inner, after]
    other_line = E("w", 0, 10, line="XLA Modules")
    assert set(tr.leaves([outer, inner, other_line])) == {inner, other_line}


def test_short_names():
    assert tr.short_name("%copy.2 = f32[1000,64,56,56]{0,3,2,1:T(8,128)} "
                         "copy(f32[1000,64,56,56]{1,0,3,2:T(8,128)} %s)") \
        == "%copy.2 copy f32[1000,64,56,56]"
    assert tr.short_name("%while.5 = (s32[]{:T(128)}, bf16[64]) "
                         "while((s32[], bf16[64]) %t), body=%b") \
        == "%while.5 while (tuple)"


def test_custom_calls_are_recognised():
    assert tr.is_custom_call("%c.1 = f32[8]{0} custom-call(f32[8]{0} %a), "
                             "custom_call_target=\"tpu_custom_call\"")
    assert not tr.is_custom_call("%custom-call.3 = f32[8]{0} fusion(%a)")


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "conv_body_v5e.xplane.pb")


def test_recorded_chip_trace():
    """Three passes of conv2_x..conv5_x at batch 1000 on a TPU v5e, traced
    with the profiler (the harness's spans ``bench/pass`` around each
    pass). Counted from the raw events by hand: the device plane has 72 XLA
    ops, none overlapping, 12 of them custom calls (one Pallas kernel per
    layer and pass) summing to 48,097,913 ns, the other 60 to 58,946,122 ns;
    so busy is their sum, 107,044,035 ns. The first op starts at
    45,974,212 ns, 1.0 ms before the first pass span (the clocks' offset),
    and the last pass span ends at 156,692,747 ns, after the last op: the
    window is 110,718,535 ns. Twelve program executions, three of each of
    the four conv programs."""
    t = tr.load(DATA, window_span="bench/pass")
    assert t.window_s() == pytest.approx(110_718_535e-9)
    assert len(t.ops) == 72
    assert t.busy_s() == pytest.approx(107_044_035e-9)
    assert t.op_seconds(custom_call=True) == pytest.approx(48_097_913e-9)
    assert t.op_seconds(custom_call=False) == pytest.approx(58_946_122e-9)
    assert sum(e.custom_call for e in t.ops) == 12
    assert len(t.modules) == 12
    runs = t.module_seconds("jit__unknown")  # names carry the program's id
    assert len(runs) == 12 and sum(runs) == pytest.approx(0.10704419, rel=1e-6)
    name, secs = t.top_ops(1)[0]
    assert name == "%_unknown_.1 custom-call f32[56,56,1008,128]"
    assert secs == pytest.approx(3 * 10.02e-3, rel=0.01)
    gaps = t.idle_gaps(3)
    assert sum(g for _, g in t.idle_gaps(100)) == pytest.approx(
        (110_718_535 - 107_044_035) * 1e-9)
    assert all(label.startswith("bench/pass") or "outside" in label
               for label, _ in gaps)
