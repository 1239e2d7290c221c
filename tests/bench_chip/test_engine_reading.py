"""The serving engine's spans and counters as the benchmark reads them: the
host-device offset and the idle time under spans on a trace worked out by
hand, the five readers on a synthetic reading, and the readers through the
harness's traced run of a tiny job on the CPU."""

import types

import pytest

from benchmarks.chip import engine_reading as er
from benchmarks.chip import trace as tr
from repro.serving.engine import ServeStats

from conftest import TINY_JOBS

MS = 1_000_000  # ns
SERVE_METRICS = ("slot_occupancy.serve", "kv_read_ratio.serve",
                 "prefill_pad.serve", "idle_step.serve", "idle_admit.serve")


def host(name, start, end):
    return tr.Event(name, start * MS, end * MS, "/host:CPU", "python")


def device(name, start, end, line="XLA Ops"):
    return tr.Event(name, start * MS, end * MS, "/device:TPU:0", line)


def synthetic(modules=("jit_decode(3)", "jit_decode(3)")):
    """Window [0, 100) ms. The host admits a request over [0, 10) and runs
    two decode rounds, ``serve/step`` [10, 50) and [50, 90), each dispatching
    ``serve/decode`` at its start (10 and 50). The device's clock reads
    1 ms ahead: the prefill ran over [2, 6) and the two decode programs
    over [10, 40) and [50, 80) on the host's clock, and the trace records
    each 1 ms earlier."""
    ops = [device("prefill", 1, 5), device("decode", 9, 39),
           device("decode", 49, 79)]
    mods = [device(m, s, e, "XLA Modules")
            for m, (s, e) in zip(modules, ((9, 39), (49, 79)))]
    spans = [host("bench/window", 0, 100), host("serve/admit#rid=3#", 0, 10),
             host("serve/prefill", 1, 2), host("serve/step", 10, 50),
             host("serve/decode", 10, 12), host("serve/step", 50, 90),
             host("serve/decode", 50, 52)]
    return tr.Trace(ops, mods, spans, (0, 100 * MS))


def test_offset_and_idle_under_by_hand():
    """The decode programs start 1 ms before their dispatch spans, so the
    offset is -1 ms. As recorded, the device is idle over [0, 1), [5, 9),
    [39, 49) and [79, 100): 21 ms of it under the steps ([39, 49) and
    [79, 90)), 5 ms under the admission ([0, 1) and [5, 9)). Moved 1 ms
    later, it is idle over [0, 2), [6, 10), [40, 50) and [80, 100): 20 ms
    under the steps, 6 ms under the admission."""
    t, said = synthetic(), []
    offset = er.host_offset_ns(t, said.append)
    assert offset == -1 * MS
    assert "-1.0000 ms" in said[-1]
    assert er.idle_under(t, "serve/step") == pytest.approx(21e-3)
    assert er.idle_under(t, "serve/admit") == pytest.approx(5e-3)
    assert er.idle_under(t, "serve/step", offset) == pytest.approx(20e-3)
    assert er.idle_under(t, "serve/admit", offset) == pytest.approx(6e-3)
    assert er.idle_under(t, "serve/nothing", offset) is None


def test_no_offset_where_spans_and_programs_do_not_pair():
    t, said = synthetic(modules=("jit_decode(3)", "jit_prefill(2)")), []
    assert er.host_offset_ns(t, said.append) == 0
    assert "2 serve/decode spans against 1 jit_decode" in said[-1]
    assert "no host-device offset" in said[-1]


def reader(name):
    from benchmarks.chip import spec

    return spec.load_module(f"{spec.HERE}/metrics/{name}.py").read


def test_readers_on_a_synthetic_reading():
    """Idle under the steps 20 of 100 ms, under the admission 6; counters
    of 6 active row-steps in 9, 192 KV positions read for 51 live, 64
    prefilled positions for 28 real. The counters are those of the
    ``system`` the harness holds while it calls the readers."""
    system = types.SimpleNamespace(engine=types.SimpleNamespace(  # noqa: F841
        stats=ServeStats(steps=3, row_steps_active=6, row_steps_total=9,
                         kv_read=192, kv_live=51, prefill_real=28,
                         prefill_padded=64)))
    said = []
    r = types.SimpleNamespace(trace=synthetic(), info={}, say=said.append)
    got = {name: reader(name)(r) for name in SERVE_METRICS}
    assert got == pytest.approx({
        "slot_occupancy.serve": 100 * 6 / 9, "kv_read_ratio.serve": 192 / 51,
        "prefill_pad.serve": 100 * 36 / 64, "idle_step.serve": 20.0,
        "idle_admit.serve": 6.0})
    # the prefill span [1, 2) lies in the idle stretch [0, 2)
    assert ("trace: device idle under serve/step 20.000 ms: serve/decode "
            "0.000, serve/sample 0.000, serve/read 0.000, serve/record 0.000, "
            "elsewhere 20.000") in said
    assert ("trace: device idle under serve/admit 6.000 ms: serve/prefill "
            "1.000, serve/insert 0.000, serve/first_token 0.000, elsewhere "
            "5.000") in said


def test_readers_find_nothing_in_a_program_without_spans_or_counters():
    """What an engine without ``stats`` and a trace without ``serve/*``
    spans give: every reader returns None, and none raises."""
    system = types.SimpleNamespace(engine=object())  # noqa: F841
    t = tr.Trace([device("decode", 9, 39)], [], [host("bench/job", 0, 100)],
                 (0, 100 * MS))
    r = types.SimpleNamespace(trace=t, info={}, say=lambda m: None)
    assert {name: reader(name)(r) for name in SERVE_METRICS} == dict.fromkeys(
        SERVE_METRICS)


def test_readers_through_the_harness_traced_run(tiny_root, monkeypatch,
                                                capsys):
    """A tiny job traced by ``run.traced_run`` on the CPU, whose trace has
    no device plane: the reduction is handed the job's real host spans and
    a device that ran each decode program exactly under its ``serve/decode``
    span, recorded 1 ms early. Once the offset is applied, the device is
    idle under a step for the step's time outside its decode span, and
    under an admission for the whole admission. The counters are the
    engine's own."""
    from jax.profiler import ProfileData

    from benchmarks.chip import run, spec
    from benchmarks.chip.peaks import peaks_for

    seen = {}

    def load(path, window_span):
        hosts = [tr.Event(ev.name, int(ev.start_ns),
                          int(ev.start_ns) + int(ev.duration_ns),
                          plane.name, line.name)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name == tr.HOST_PLANE
                 for line in plane.lines for ev in line.events]
        decode = [e for e in hosts if e.name == "serve/decode"]
        ops = [tr.Event("decode", e.start - MS, e.end - MS, "/device:TPU:0",
                        tr.OPS_LINE) for e in decode]
        mods = [tr.Event("jit_decode(1)", e.start - MS, e.end - MS,
                         "/device:TPU:0", tr.MODULES_LINE) for e in decode]
        window, = [e for e in hosts if e.name == window_span]
        seen["spans"] = hosts
        return tr.Trace(ops, mods, hosts, (window.start, window.end))

    monkeypatch.setattr(tr, "load", load)
    cell = spec.resolve("tiny-lm.jobs", tiny_root)
    system = cell.kind_module().System(cell, 2**31 + 11, lambda m: None)
    system.setup()
    metrics, _, window_s, _, attempted, failed = run.traced_run(
        system, cell, peaks_for("TPU v5 lite"))
    s = system.engine.stats
    assert failed == 0 and s.admissions == attempted == TINY_JOBS[
        "job_requests"]
    got = {name: metrics[name]["value"] for name in SERVE_METRICS}
    assert got["slot_occupancy.serve"] == pytest.approx(
        100 * s.row_steps_active / s.row_steps_total)
    assert got["kv_read_ratio.serve"] == pytest.approx(s.kv_read / s.kv_live)
    assert got["prefill_pad.serve"] == pytest.approx(
        100 * (s.prefill_padded - s.prefill_real) / s.prefill_padded)
    total = lambda name: sum(e.end - e.start for e in seen["spans"]
                             if e.name == name) * 1e-9
    assert total("serve/admit") > 0 and s.steps > 0
    assert got["idle_step.serve"] == pytest.approx(
        100 * (total("serve/step") - total("serve/decode")) / window_s)
    assert got["idle_admit.serve"] == pytest.approx(
        100 * total("serve/admit") / window_s)
    assert f"{s.steps} serve/decode spans" in capsys.readouterr().err
