"""Pallas TPU kernels for the perf-critical compute hot-spots, tiled by the
``repro.plan`` planner. Every kernel accepts ``plan=`` (an ``ExecutionPlan``
from ``repro.plan.plan``) or ``target=`` (a ``HardwareTarget``); the
pre-redesign per-module planners (``plan_conv_tiles``, ``plan_tiles``) are
retired, and so is the ``use_pallas=`` shim (``kernels/ops.py``) — pick a
backend with ``repro.ops.ExecutionContext``. Validated against the pure-jnp
oracles in ref.py; ``repro.plan.pallas_interpret`` decides, from the backend
the kernels execute on, whether they run interpreted (the CPU) or compiled
through Mosaic (the TPU).

Consumers should not call these modules directly: the ``repro.ops`` dispatch
subsystem (ExecutionContext -> Backend -> kernel) routes each call to the
right backend with capability fallback and attaches measured HBM-word
counters (``conv2d_hbm_words``, ``matmul_hbm_words``, ``im2col_hbm_words``,
``attention_hbm_words``, ``paged_decode_hbm_words``) to every instrumented
dispatch."""

from . import ref  # noqa: F401
from .conv1d import conv1d_causal  # noqa: F401
from .conv2d import conv2d, conv2d_hbm_words  # noqa: F401
from .flash_attention import (  # noqa: F401
    attention_blocks,
    attention_hbm_words,
    flash_attention,
    paged_decode_attention,
    paged_decode_hbm_words,
)
from .im2col import conv2d_im2col, im2col_hbm_words  # noqa: F401
from .matmul import matmul, matmul_hbm_words  # noqa: F401
