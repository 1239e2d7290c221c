"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The trace is read in process with ``jax.profiler.ProfileData``. From each
device plane (``/device:TPU:<n>``) it takes the operations that ran on the
device (the ``XLA Ops`` line) and the program executions (the ``XLA
Modules`` line); from the host plane, the harness's own spans (names that
start with ``bench/``) and what each host thread was doing. Everything is
clipped to the window: the harness span named when the trace is loaded,
widened to the device ops just outside it that the clocks' offset moved.

- busy: the union of the intervals in which an operation ran, averaged over
  the device planes that ran anything;
- idle gaps: the stretches of the window with no operation on the device,
  each labelled with the innermost harness span that covers its middle and
  the innermost host event of that span's thread there;
- per-op device time, split by whether the op is a custom call (a Pallas
  kernel) or an XLA operation.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
# The device's and the host's clocks in one trace differ by about a
# millisecond on a v5e (a pass's first op shows before the host span that
# dispatched it), so the window also takes in the device ops within this
# distance of the host span.
CLOCK_SLACK_NS = 10_000_000


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    plane: str = ""
    line: str = ""
    custom_call: bool = False


def is_custom_call(name: str) -> bool:
    """A Pallas kernel shows as a custom call: the op's name is its HLO
    text, ``%x = f32[...] custom-call(...), custom_call_target=...``."""
    return " custom-call(" in name


_HLO = re.compile(r"^(%?[\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([a-z][\w\-]*)\(")
_HLO_TUPLE = re.compile(r"^(%?[\w.\-]+) = \(.*?\) ([a-z][\w\-]*)\(")


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 fusion f32[8,128]``; a tuple result reads ``(tuple)``.
    Anything else is returned as it is."""
    m = _HLO.match(hlo)
    if m:
        return f"{m.group(1)} {m.group(3)} {m.group(2)}"
    m = _HLO_TUPLE.match(hlo)
    if m:
        return f"{m.group(1)} {m.group(2)} (tuple)"
    return hlo


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def leaves(events: List[Event]) -> List[Event]:
    """The events of each (plane, line) that enclose no other event."""
    out: List[Event] = []
    groups: Dict[Tuple[str, str], List[Event]] = collections.defaultdict(list)
    for e in events:
        groups[(e.plane, e.line)].append(e)
    for group in groups.values():
        group.sort(key=lambda e: (e.start, -e.end))
        for i, e in enumerate(group):
            j, parent = i + 1, False
            while j < len(group) and group[j].start < e.end:
                if group[j].end <= e.end:
                    parent = True
                    break
                j += 1
            if not parent:
                out.append(e)
    return out


class Trace:
    def __init__(self, ops: List[Event], modules: List[Event],
                 host: List[Event], window: Tuple[int, int]):
        self.window = window
        lo, hi = window
        clip = [e for e in ops if e.end > lo and e.start < hi]
        self.ops = [dataclasses.replace(e, start=max(e.start, lo),
                                        end=min(e.end, hi)) for e in clip]
        # a control-flow op (a while loop) encloses the ops of its body on
        # the same line: sums of op time count the innermost ops alone
        self.leaf_ops = leaves(self.ops)
        self.modules = [e for e in modules if e.end > lo and e.start < hi]
        self.host = host
        self.planes = sorted({e.plane for e in self.ops})

    # -- totals ---------------------------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals, averaged over the planes that ran ops."""
        if not self.planes:
            return 0.0
        per = [union_ns([(e.start, e.end) for e in self.ops if e.plane == p])
               for p in self.planes]
        return sum(per) / len(per) * 1e-9

    def op_seconds(self, custom_call: Optional[bool] = None,
                   where: Optional[Callable[[Event], bool]] = None) -> float:
        """Summed device time of the innermost ops, per plane averaged: all
        of them, only custom calls, or only XLA ops, optionally filtered
        further."""
        if not self.planes:
            return 0.0
        tot = sum(e.end - e.start for e in self.leaf_ops
                  if (custom_call is None or e.custom_call == custom_call)
                  and (where is None or where(e)))
        return tot / len(self.planes) * 1e-9

    def module_seconds(self, program: str) -> List[float]:
        """Durations of the executions of the program named ``program``
        (``jit_decode`` matches ``jit_decode`` and ``jit_decode(123)``, not
        ``jit_decode_step``)."""
        pattern = re.compile(re.escape(program) + r"(\(.*\))?$")
        return [(e.end - e.start) * 1e-9 for e in self.modules
                if pattern.match(e.name)]

    # -- breakdown ------------------------------------------------------------
    def top_ops(self, k: int) -> List[list]:
        """The ``k`` ops that took the most device time, each [instruction,
        opcode and result type, seconds]. Ops of that one short name are
        summed: the same instruction in programs that differ only in a
        shape elsewhere (the decode step at each table width) is one op."""
        tot: Dict[str, int] = collections.Counter()
        for e in self.leaf_ops:
            tot[short_name(e.name)] += e.end - e.start
        n = max(len(self.planes), 1)
        return [[name, t / n * 1e-9] for name, t in tot.most_common(k)]

    def _label(self, t: int) -> str:
        spans = [e for e in self.host if e.start <= t < e.end
                 and e.name.startswith(SPAN_PREFIX)]
        if not spans:
            return "outside the harness's spans"
        span = max(spans, key=lambda e: (e.start, -e.end))
        inner = [e for e in self.host if e.line == span.line
                 and e.start <= t < e.end and not e.name.startswith(SPAN_PREFIX)
                 and e.start >= span.start]
        if not inner:
            return span.name
        return f"{span.name} > {max(inner, key=lambda e: e.start).name}"

    def idle_gaps(self, k: int) -> List[list]:
        """The ``k`` longest idle stretches of the first device plane, each
        [label, seconds]; labels name what the host was doing."""
        if not self.planes:
            return []
        lo, hi = self.window
        ivs = [(e.start, e.end) for e in self.ops if e.plane == self.planes[0]]
        gaps = sorted(gaps_ns(ivs, lo, hi), key=lambda g: g[0] - g[1])[:k]
        return [[self._label((s + e) // 2), (e - s) * 1e-9] for s, e in gaps]


def load(path: str, window_span: str) -> Trace:
    """Read ``path``; the window runs from the first host span named
    ``window_span`` to the last, widened to take in the device ops within
    ``CLOCK_SLACK_NS`` of it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Event] = []
    seen_lines = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        seen_lines[plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            for line in lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = Event(ev.name, s, s + int(ev.duration_ns), plane.name,
                              line.name)
                    if line.name == OPS_LINE:
                        ops.append(dataclasses.replace(
                            e, custom_call=is_custom_call(ev.name)))
                    else:
                        modules.append(e)
        elif plane.name == HOST_PLANE:
            for line in lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append(Event(ev.name, s, s + int(ev.duration_ns),
                                      plane.name, line.name))
    spans = [e for e in host if e.name == window_span]
    if not spans:
        raise ValueError(f"no {window_span!r} span in the trace; planes and "
                         f"lines: {seen_lines}")
    if not ops:
        raise ValueError(f"no device operations in the trace; planes and "
                         f"lines: {seen_lines}")
    lo, hi = min(e.start for e in spans), max(e.end for e in spans)
    near = [e for e in ops if e.end > lo - CLOCK_SLACK_NS
            and e.start < hi + CLOCK_SLACK_NS]
    if near:
        lo = min(lo, min(e.start for e in near))
        hi = max(hi, max(e.end for e in near))
    return Trace(ops, modules, host, (lo, hi))
