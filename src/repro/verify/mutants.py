"""Seeded mutants — the auditor's own regression harness.

Each mutant injects a realistic kernel bug into otherwise-correct launch
metadata and asserts the static checks catch it:

  * ``halo_off_by_one``   shifts the conv2d input halo window's h start by
    one row — the classic stride/halo index-map bug. The word *totals* are
    unchanged, so only the ``requires``-coverage check can see it.
  * ``dropped_dma_wait``  removes the WAIT events from the double-buffered
    schedule — the kernel would read stale VMEM (H1).
  * ``same_slot_prefetch`` prefetches step ci+1 into the slot step ci is
    about to consume — the overlap bug double buffering exists to prevent
    (H2/H3).
  * ``write_back_before_wait`` copies the paged decode's written page back
    to HBM before its own copy in has landed — the page would go back
    stale, the new row over an old page (H6).
  * ``scale_applied_twice`` makes the quantized conv's folded scale vector
    re-fetch on every reduction step instead of once — the classic
    dequantize-in-the-loop bug. Totals move (counted > words_fn), so the
    counted-vs-measured exactness check must flag it.
  * ``fault_swallowed``     a handler catches an injected NumericFault and
    silently eats it — no retry, no row failure, no record. The campaign's
    resolution accounting (``FaultCampaign.unresolved`` /
    ``verify_accounted``) must flag the swallowed injection.

``run_seeded_mutants()`` returns ``(name, caught, detail)`` triples;
``scripts/verify.py --mutants`` (and the CI verify job) fail unless every
mutant is caught.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from . import audit as _audit
from . import hazards as hz
from .access import BlockAccess, KernelAccessPlan, WindowAccess


def _conv2d_plan() -> KernelAccessPlan:
    """A representative strided conv2d access plan (ResNet conv3_1-like)."""
    from repro.kernels.conv2d import conv2d_access_plan

    x = jax.ShapeDtypeStruct((8, 64, 56, 56), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 64, 3, 3), jnp.bfloat16)
    return conv2d_access_plan(x, w, stride=(2, 2))


def halo_off_by_one() -> Tuple[bool, str]:
    """Shift the input halo window one row down; words stay identical."""
    ap = _conv2d_plan()
    mutated = []
    for acc in ap.accesses:
        if isinstance(acc, WindowAccess) and acc.name == "input":
            orig = acc.window

            def shifted(*axes, _orig=orig):
                win = list(_orig(*axes))
                (h0, hs) = win[2]
                win[2] = (h0 + 1, hs)  # off-by-one h start
                return tuple(win)

            acc = dataclasses.replace(acc, window=shifted)
        mutated.append(acc)
    report = _audit.audit_access_plan(
        dataclasses.replace(ap, accesses=tuple(mutated)))
    caught = any("misses the required" in p or "exceeds the padded extent" in p
                 for p in report.problems)
    return caught, "; ".join(report.problems[:2]) or "not detected"


def dropped_dma_wait() -> Tuple[bool, str]:
    """Strip the WAIT events: compute reads data the DMA never landed."""
    sched = hz.double_buffered_schedule(6, name="mutant:no-wait")
    mutated = dataclasses.replace(
        sched, events=tuple(e for e in sched.events if e.kind != hz.WAIT))
    found = hz.check_schedule(mutated)
    caught = any(h.code == "H1" for h in found)
    return caught, "; ".join(str(h) for h in found[:2]) or "not detected"


def same_slot_prefetch() -> Tuple[bool, str]:
    """Prefetch ci+1 into the slot step ci still consumes (n_slots=1 bug)."""
    sched = hz.double_buffered_schedule(6, name="mutant:same-slot")
    mutated = dataclasses.replace(
        sched, events=tuple(
            dataclasses.replace(e, slot=0) if e.kind == hz.START else e
            for e in sched.events))
    found = hz.check_schedule(mutated)
    caught = any(h.code in ("H2", "H3") for h in found)
    return caught, "; ".join(str(h) for h in found[:2]) or "not detected"


def write_back_before_wait() -> Tuple[bool, str]:
    """Start the paged decode's page write-back ahead of the page's wait."""
    sched = hz.double_buffered_schedule(4, name="mutant:early-store",
                                        write_back=True)
    ev = list(sched.events)
    store = next(i for i, e in enumerate(ev) if e.kind == hz.STORE)
    ev.insert(store - 1, ev.pop(store))  # before the WAIT that precedes it
    found = hz.check_schedule(dataclasses.replace(sched, events=tuple(ev)))
    caught = any(h.code == "H6" for h in found)
    return caught, "; ".join(str(h) for h in found[:2]) or "not detected"


def scale_applied_twice() -> Tuple[bool, str]:
    """The dequantize-at-every-application bug: the kernel fetches the
    folded scale vector once per application site (per-tap AND at the final
    store) instead of holding it resident, doubling the scale stream. The
    words_fn charges the vector exactly once, so the counted-vs-measured
    exactness check in ``audit_decision`` must fire."""
    from repro import ops
    from repro.kernels.quant import conv2d_q_access_plan
    from repro.plan import TPU_V5E

    x = jax.ShapeDtypeStruct((8, 64, 56, 56), jnp.int8)
    w = jax.ShapeDtypeStruct((128, 64, 3, 3), jnp.int8)
    s = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    ctx = ops.ExecutionContext(target=TPU_V5E, backend="pallas")
    decision = ops.explain("conv2d_q", ctx=ctx, dtype="int8",
                           spec_args=(x, w, s), spec_kw={"stride": (2, 2)})
    ap = conv2d_q_access_plan(x, w, s, stride=(2, 2), plan=decision.plan)

    extra = tuple(
        dataclasses.replace(acc, name="scale(second application)")
        for acc in ap.accesses
        if isinstance(acc, BlockAccess) and acc.name == "scale")
    assert extra, "conv2d_q access plan no longer carries a scale operand"
    report = _audit.audit_decision(
        dataclasses.replace(ap, accesses=ap.accesses + extra), decision)
    caught = any("!= words_fn" in p for p in report.problems)
    return caught, "; ".join(report.problems[:2]) or "not detected"


def fault_swallowed() -> Tuple[bool, str]:
    """A fault handler that catches an injected NumericFault and silently
    swallows it — the recovery bug the resolution accounting exists for.
    Every legitimate handler stamps ``Injection.resolution`` (retried /
    row_failed / degraded / ...); this one stamps nothing, so the campaign
    must report the injection as unresolved."""
    from repro.resilience.errors import NumericFault
    from repro.resilience.faults import FaultCampaign

    camp = FaultCampaign(seed=0, rate=1.0, kinds=("numeric",), max_faults=1)
    inj = camp.draw("dispatch/conv2d", op="conv2d")
    assert inj is not None, "rate-1.0 campaign failed to inject"
    try:
        raise camp.fault_for(inj, op="conv2d", backend="pallas")
    except NumericFault:
        pass  # the mutant: no resolve(), no retry, no row failure
    leaks = camp.unresolved()
    caught = bool(leaks)
    return caught, (f"{len(leaks)} unresolved injection(s): "
                    f"{leaks[0].kind} at {leaks[0].site}" if caught
                    else "not detected")


MUTANTS: Tuple[Tuple[str, Callable[[], Tuple[bool, str]]], ...] = (
    ("halo_off_by_one", halo_off_by_one),
    ("dropped_dma_wait", dropped_dma_wait),
    ("same_slot_prefetch", same_slot_prefetch),
    ("write_back_before_wait", write_back_before_wait),
    ("scale_applied_twice", scale_applied_twice),
    ("fault_swallowed", fault_swallowed),
)


def run_seeded_mutants() -> List[Tuple[str, bool, str]]:
    return [(name, *fn()) for name, fn in MUTANTS]
