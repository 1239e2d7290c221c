"""What the serving engine's own spans and counters say about a traced job.

The engine (``repro.serving.engine``) opens ``serve/*`` profiler spans on
the host plane of the same trace as the device ops, and counts its work in
``Engine.stats``. This module puts the spans on the device's clock, reads
the device's idle time under them, and finds the counters of the traced
job. Each function returns None, and raises nothing, where the program has
no such spans or counters.
"""

from __future__ import annotations

import re
import sys
from typing import List, Optional

from benchmarks.chip import trace as tr


def _spans(trace: tr.Trace, prefix: str) -> List[tr.Event]:
    """Host events inside the window whose name starts with ``prefix`` (so
    also where the name carries the profiler's ``#key=value#`` metadata)."""
    lo, hi = trace.window
    return [e for e in trace.host if e.name.startswith(prefix)
            and e.end > lo and e.start < hi]


def host_offset_ns(trace: tr.Trace, say, span: str = "serve/decode",
                   program: str = "jit_decode") -> int:
    """How far the device's clock reads ahead of the host's, in ns (zero or
    negative). The k-th ``span`` in the window dispatched the k-th execution
    of ``program``, and no program starts before its dispatch, so the offset
    is ``min(0, min_k(device_start_k - span_start_k))``. Where the counts
    differ, no pairing holds and the offset is 0. Says which on ``say``."""
    spans = sorted(e.start for e in _spans(trace, span))
    pattern = re.compile(re.escape(program) + r"(\(.*\))?$")
    first = trace.planes[0] if trace.planes else None
    runs = sorted(e.start for e in trace.modules
                  if e.plane == first and pattern.match(e.name))
    if not spans or len(spans) != len(runs):
        say(f"trace: {len(spans)} {span} spans against {len(runs)} {program} "
            f"executions: no host-device offset applied")
        return 0
    lag = min(d - s for s, d in zip(spans, runs))
    say(f"trace: host-device offset {min(0, lag) * 1e-6:.4f} ms: of "
        f"{len(spans)} {span} spans, the least lag to the start of their "
        f"{program} execution is {lag * 1e-6:.4f} ms")
    return min(0, lag)


def idle_under(trace: tr.Trace, span_prefix: str,
               offset: int = 0) -> Optional[float]:
    """Seconds of the window in which no op ran on the device and the host
    was inside a span whose name starts with ``span_prefix``, averaged over
    the device planes that ran ops. Device times are moved by ``-offset``
    onto the host's clock first. None where the trace has no such span.

    Within the window, idle time under spans S is what S adds to the busy
    time B: ``|S | B| - |B|``."""
    spans = _spans(trace, span_prefix)
    if not spans or not trace.planes:
        return None
    lo, hi = trace.window

    def clip(intervals):
        out = [(max(s, lo), min(e, hi)) for s, e in intervals]
        return [(s, e) for s, e in out if e > s]

    under = clip((e.start, e.end) for e in spans)
    per = []
    for plane in trace.planes:
        busy = clip((e.start - offset, e.end - offset) for e in trace.ops
                    if e.plane == plane)
        per.append(tr.union_ns(under + busy) - tr.union_ns(busy))
    return sum(per) / len(per) * 1e-9


def idle_share(r, span: str, children) -> Optional[float]:
    """The share of the window, in %, in which the device was idle under
    ``span`` once on the host's clock; says how much of that idle time lies
    under each of the span's ``children``. None where there is no ``span``."""
    trace = r.trace
    offset = host_offset_ns(trace, r.say)
    idle = idle_under(trace, span, offset)
    if idle is None or trace.window_s() <= 0:
        return None
    parts = [(c, idle_under(trace, c, offset) or 0.0) for c in children]
    r.say(f"trace: device idle under {span} {idle * 1e3:.3f} ms: "
          + ", ".join(f"{c} {t * 1e3:.3f}" for c, t in parts)
          + f", elsewhere {(idle - sum(t for _, t in parts)) * 1e3:.3f}")
    return 100.0 * idle / trace.window_s()


def engine_stats():
    """``Engine.stats`` of the engine that served the traced job, or None.

    A metric's reading holds what the cell's ``System.traced()`` returned,
    which carries no engine counters. The harness calls the readers from
    ``run.traced_run``, whose ``system`` holds the cell's ``engine``; the
    traced job is that engine's last ``serve`` call, and ``Engine.stats``
    counts the last call alone. So the counters are taken from there."""
    frame = sys._getframe(1)
    while frame is not None:
        engine = getattr(frame.f_locals.get("system"), "engine", None)
        stats = getattr(engine, "stats", None)
        if stats is not None:
            return stats
        frame = frame.f_back
    return None
