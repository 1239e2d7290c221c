"""DMA-hazard detector for double-buffered copy schedules.

The manual ``make_async_copy`` pipelines in ``kernels/conv2d.py``,
``kernels/matmul.py`` and the paged decode's page loop
(``kernels/flash_attention.py``) follow one shape: per reduction step
``ci`` warm up slot 0 on the first step, prefetch step ``ci+1`` into the
other slot, wait on ``ci``'s slot, then read it. The paged decode also
writes one landed page back to HBM, the one that takes the step's new
k/v row, and drains that copy before the slot is refilled.
:func:`double_buffered_schedule` emits that event stream;
:func:`check_schedule` simulates it and reports every hazard:

  H1 read-before-wait      a step reads slot data it never waited for
  H2 double-start          two in-flight copies target one slot
  H3 reuse-distance        a slot is refilled < n_slots steps after its
                           previous fill (the prefetch would race the
                           compute still consuming it)
  H4 inflight-read         a copy is in flight into a slot the current
                           grid step reads
  H5 dangling-start        an in-flight copy (in or back out) is never
                           waited before the schedule ends
  H6 write-back-unlanded   a page is copied back out of a slot before its
                           own copy in was waited, or with a drain that
                           matches no write-back
  H7 refill-under-store    a slot is refilled while its write-back still
                           reads it
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

START, WAIT, READ = "start", "wait", "read"
STORE, DRAIN = "store", "drain"  # a landed slot's copy back out, its wait


@dataclasses.dataclass(frozen=True)
class DmaEvent:
    kind: str  # start | wait | read | store | drain
    slot: int
    step: int  # reduction-step payload the event moves/consumes


@dataclasses.dataclass(frozen=True)
class DmaSchedule:
    """Event stream for one double-buffered operand stream."""

    n_slots: int
    n_steps: int
    events: Tuple[DmaEvent, ...]
    name: str = "stream"


@dataclasses.dataclass(frozen=True)
class Hazard:
    code: str  # H1..H7
    event_index: int  # -1 for end-of-schedule hazards
    message: str

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return f"{self.code}@{self.event_index}: {self.message}"


def double_buffered_schedule(n_steps: int, n_slots: int = 2,
                             name: str = "stream",
                             write_back: bool = False) -> DmaSchedule:
    """The schedule the conv and matmul kernels issue across the reduction
    axis. ``write_back``: the last step's page, once landed, takes the new
    row and is copied back out (store), read, and the copy drained — the
    paged decode's page that holds the row's newest position."""
    ev: List[DmaEvent] = []
    for ci in range(n_steps):
        slot = ci % n_slots
        if ci == 0:
            ev.append(DmaEvent(START, 0, 0))
        if ci + 1 < n_steps:
            ev.append(DmaEvent(START, (ci + 1) % n_slots, ci + 1))
        ev.append(DmaEvent(WAIT, slot, ci))
        back = write_back and ci == n_steps - 1
        if back:
            ev.append(DmaEvent(STORE, slot, ci))
        ev.append(DmaEvent(READ, slot, ci))
        if back:
            ev.append(DmaEvent(DRAIN, slot, ci))
    return DmaSchedule(n_slots=n_slots, n_steps=n_steps, events=tuple(ev),
                       name=name)


def check_schedule(sched: DmaSchedule) -> List[Hazard]:
    """Simulate the event stream; return every hazard found (empty = clean)."""
    n = sched.n_slots
    inflight: List[Optional[int]] = [None] * n  # step being copied into slot
    ready: List[Optional[int]] = [None] * n  # step landed in slot
    unread: List[bool] = [False] * n  # landed but not yet consumed
    last_fill: List[Optional[int]] = [None] * n  # step of previous fill
    storing: List[Optional[int]] = [None] * n  # step being copied back out
    hazards: List[Hazard] = []

    def bad(code: str, i: int, msg: str) -> None:
        hazards.append(Hazard(code, i, f"[{sched.name}] {msg}"))

    for i, ev in enumerate(sched.events):
        if ev.slot < 0 or ev.slot >= n:
            bad("H2", i, f"event targets slot {ev.slot} outside 0..{n - 1}")
            continue
        if ev.kind == START:
            if inflight[ev.slot] is not None:
                bad("H2", i, f"start(step {ev.step}) while step "
                             f"{inflight[ev.slot]} is still in flight into "
                             f"slot {ev.slot}")
            if unread[ev.slot]:
                bad("H3", i, f"start(step {ev.step}) overwrites slot "
                             f"{ev.slot} before step {ready[ev.slot]} was "
                             f"read")
            if (last_fill[ev.slot] is not None
                    and ev.step - last_fill[ev.slot] < n):
                bad("H3", i, f"slot {ev.slot} reused after "
                             f"{ev.step - last_fill[ev.slot]} steps "
                             f"(< {n} buffers)")
            if storing[ev.slot] is not None:
                bad("H7", i, f"start(step {ev.step}) refills slot {ev.slot} "
                             f"while step {storing[ev.slot]} is still being "
                             f"copied back out of it")
            inflight[ev.slot] = ev.step
            last_fill[ev.slot] = ev.step
        elif ev.kind == WAIT:
            if inflight[ev.slot] != ev.step:
                bad("H1", i, f"wait(step {ev.step}, slot {ev.slot}) without "
                             f"a matching start (in flight: "
                             f"{inflight[ev.slot]})")
            else:
                inflight[ev.slot] = None
                ready[ev.slot] = ev.step
                unread[ev.slot] = True
        elif ev.kind == READ:
            if inflight[ev.slot] is not None:
                bad("H4", i, f"step {ev.step} reads slot {ev.slot} while "
                             f"step {inflight[ev.slot]} is being copied "
                             f"into it")
            if ready[ev.slot] != ev.step:
                bad("H1", i, f"step {ev.step} reads slot {ev.slot} but the "
                             f"slot holds "
                             f"{'nothing' if ready[ev.slot] is None else f'step {ready[ev.slot]}'}"
                             f" (missing wait)")
            unread[ev.slot] = False
        elif ev.kind == STORE:
            if inflight[ev.slot] is not None or ready[ev.slot] != ev.step:
                bad("H6", i, f"step {ev.step} is copied back out of slot "
                             f"{ev.slot} before it landed there")
            storing[ev.slot] = ev.step
        elif ev.kind == DRAIN:
            if storing[ev.slot] != ev.step:
                bad("H6", i, f"drain(step {ev.step}, slot {ev.slot}) "
                             f"without a matching write-back")
            storing[ev.slot] = None
        else:  # pragma: no cover - malformed schedule
            bad("H1", i, f"unknown event kind {ev.kind!r}")
    for slot, step in enumerate(inflight):
        if step is not None:
            hazards.append(Hazard(
                "H5", -1, f"[{sched.name}] copy of step {step} into slot "
                          f"{slot} never waited before schedule end"))
    for slot, step in enumerate(storing):
        if step is not None:
            hazards.append(Hazard(
                "H5", -1, f"[{sched.name}] write-back of step {step} out of "
                          f"slot {slot} never drained before schedule end"))
    return hazards
