"""Structured kernel-launch metadata: what each Pallas launch moves and when.

Every registered Pallas kernel exposes a ``<kernel>_access_plan`` builder
returning a :class:`KernelAccessPlan` — the grid, the per-operand HBM access
pattern (BlockSpec ``index_map``s for pipelined operands, explicit halo
windows for manual-DMA operands, flat word counts for scalar prefetch), the
VMEM scratch allocations, and the double-buffered DMA schedule. The plan is
pure data built from the same geometry helpers the kernel lowering uses, so
``repro.verify.audit`` can abstractly interpret it — walk the grid, count
exact HBM words, check bounds/coverage — without touching a device.

Word unit everywhere: 32-bit words (``itemsize / 4`` per element), matching
the ``*_hbm_words`` counters and the Thm 2.1 bounds.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class BlockAccess:
    """A pipelined ``pl.BlockSpec`` operand.

    ``index_map`` is the spec's index map, vectorizable over numpy arrays:
    called with one array per grid axis (all the same flat length) it must
    return one block-index array/scalar per array dimension. Pallas only
    re-fetches (re-stores) a block when the mapped index changes between
    consecutive grid steps, so audited words = index-transition count x
    block words.
    """

    name: str
    kind: str  # "load" | "store"
    block_shape: Tuple[int, ...]  # elements moved per (re)visit
    array_shape: Tuple[int, ...]  # padded element extent in HBM
    index_map: Callable  # (*grid_axes) -> per-dim block indices
    word_size: float  # 32-bit words per element
    counted: bool = True  # charged by the op's words_fn
    note: str = ""


@dataclasses.dataclass(frozen=True)
class WindowAccess:
    """A manual ``make_async_copy`` operand (the halo-window streams).

    ``window`` maps grid indices to one ``(start, size)`` pair per array
    dimension (vectorizable; ``size`` is static per plan). The copy issues
    every grid step — no revisit elision — so words = n_steps x copies x
    window words. ``requires`` independently derives the element range op
    semantics need at that step; the auditor checks requires ⊆ window,
    which is what catches an off-by-one halo index map even when the word
    *totals* stay unchanged. ``copies``, where set, declares that many
    copies per grid step (the paged decode's page loop): ``window`` and
    ``requires`` then take one more trailing argument, the copy's index.
    """

    name: str
    kind: str  # "load" | "store"
    window: Callable  # (*grid_axes) -> ((start, size), ...) per dim
    array_shape: Tuple[int, ...]
    word_size: float
    requires: Optional[Callable] = None  # (*grid_axes) -> ((lo, hi), ...)
    counted: bool = True
    note: str = ""
    copies: Optional[int] = None  # copies per grid step; None: one


@dataclasses.dataclass(frozen=True)
class FlatAccess:
    """Traffic with no per-step structure: scalar-prefetch operands and
    one-shot materializations (the im2col patch expansion). ``counted``
    mirrors whether the op's ``words_fn`` charges it."""

    name: str
    kind: str  # "load" | "store"
    words: float
    counted: bool = True
    note: str = ""


Access = Union[BlockAccess, WindowAccess, FlatAccess]


@dataclasses.dataclass(frozen=True)
class ScratchAlloc:
    """One VMEM scratch buffer (words, 32-bit)."""

    name: str
    words: float


@dataclasses.dataclass(frozen=True)
class KernelAccessPlan:
    """Everything one Pallas launch does to memory, as pure data."""

    op: str
    grid: Tuple[int, ...]
    accesses: Tuple[Access, ...]
    scratch: Tuple[ScratchAlloc, ...] = ()
    # DMA schedule over the innermost (reduction) grid axis, None when the
    # kernel has no manual double buffering. Built by
    # hazards.double_buffered_schedule to mirror the kernel's issue order.
    dma: Optional["object"] = None  # hazards.DmaSchedule
    note: str = ""

    @property
    def n_steps(self) -> int:
        n = 1
        for g in self.grid:
            n *= int(g)
        return n

    def scratch_words(self) -> float:
        return float(sum(s.words for s in self.scratch))
