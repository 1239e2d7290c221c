"""Chip smoke test: the main paths, compiled, on one TPU (or four).

    python chip_smoke.py             # one chip: phases conv and serve
    python chip_smoke.py --chips 4   # four chips: phase dist only

Phase conv runs the five ResNet-50 layers of the paper (batch 1000, bf16)
through ``ops.conv2d`` on the ``pallas`` (LP-tiled direct conv) and
``im2col`` backends with analytic plans, and holds each to
``kernels.ref.conv2d_ref``. Phase serve drives the ``launch.serve`` code path
for qwen2.5-3b at its published widths (random bf16 weights from ``--seed``)
on the default paged ``Engine``, and compares the prefill logits of one
prompt under the pallas and xla contexts. Phase dist runs the halo-exchange
conv of conv2_x on a four-chip conv mesh against the single-device conv and
the all-gather baseline.

Everything runs in this one process: a child process could not reach a chip
this process holds. Without a TPU the script exits non-zero before any phase
runs; it never falls back to the CPU. The last line of standard output is a
JSON object ``{"ok": true, "device": {...}}`` only when every phase passed.
Times printed here are smoke wall times of single calls, not benchmark
results.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.abspath(__file__))

# Conv: both sides read the same bf16 operands (bf16 products are exact in
# f32) and accumulate in f32, so they differ only in summation order. An
# f32 sum of K terms in another order moves by about sqrt(K) * 2^-24 times
# the partial sums (< 1e-6 of max|ref| at K <= 4608); 1e-4 leaves margin
# while any indexing, halo or padding fault moves whole outputs (~1).
CONV_RTOL = 1e-4
# Serve: pallas and xla attention round their bf16 outputs in a different
# order (flash's online softmax against one softmax), one bf16 ulp (2^-8)
# of difference per layer that the 36-layer residual stream carries into
# the logits. 5e-2 of max|logit| is about a dozen ulps.
LOGIT_RTOL = 5e-2
SERVE_ARCH = "qwen2_5_3b"
SERVE_REQUESTS = 8
SERVE_PROMPT_LENS = (16, 512)
SERVE_NEW_TOKENS = 32
SERVE_MAX_LEN = 544  # longest prompt + new tokens, a whole number of blocks
DIST_LAYER = "conv2_x"
DIST_PROBE_GRID = {"hO": 2, "cI": 2}


def say(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


class Phase:
    """Collects one phase's checks; a check that fails marks the phase."""

    def __init__(self, name: str):
        self.name, self.failures = name, []

    def check(self, label: str, ok: bool, detail: str) -> None:
        say(f"[{self.name}] {'PASS' if ok else 'FAIL'} {label}: {detail}")
        if not ok:
            self.failures.append(label)

    def crash(self, label: str) -> None:
        say(f"[{self.name}] FAIL {label}: raised\n{traceback.format_exc()}")
        self.failures.append(label)

    @property
    def ok(self) -> bool:
        return not self.failures


# ---------------------------------------------------------------------------
# Phase conv
# ---------------------------------------------------------------------------

def conv_operands(shape, seed: int):
    """Seeded bf16 (x, w) for a ConvShape, made on the device."""
    H = (shape.h_O - 1) * shape.sh + shape.h_F
    W = (shape.w_O - 1) * shape.sw + shape.w_F
    fan_in = shape.c_I * shape.h_F * shape.w_F

    @jax.jit
    def make(key):
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (shape.N, shape.c_I, H, W), jnp.bfloat16)
        w = jax.random.normal(kw, (shape.c_O, shape.c_I, shape.h_F,
                                   shape.w_F), jnp.float32)
        return x, (w / fan_in ** 0.5).astype(jnp.bfloat16)

    return make(jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnames=("stride",))
def conv_error(out, x, w, stride):
    """(max |out - ref|, max |ref|) against ``conv2d_ref`` in f32, walked in
    batch chunks so the reference never holds a second full output."""
    from repro.kernels.ref import conv2d_ref

    n = x.shape[0]
    chunks = next(c for c in (10, 8, 5, 4, 2, 1) if n % c == 0)

    def one(args):
        xc, oc = args
        with jax.default_matmul_precision("highest"):
            ref = conv2d_ref(xc, w, stride)
        return jnp.max(jnp.abs(oc - ref)), jnp.max(jnp.abs(ref))

    err, mag = jax.lax.map(one, (x.reshape(chunks, n // chunks, *x.shape[1:]),
                                 out.reshape(chunks, n // chunks,
                                             *out.shape[1:])))
    return jnp.max(err), jnp.max(mag)


def compile_recorded(fn, *args):
    """(compiled, compile seconds, dispatch decisions made while tracing)."""
    from repro import ops

    with ops.record_dispatch() as log:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        secs = time.perf_counter() - t0
    return compiled, secs, list(log)


def run_conv_phase(dev, seed: int) -> Phase:
    from repro import ops
    from repro.configs.resnet50_convs import RESNET50
    from repro.plan import default_target

    ph = Phase("conv")
    target = default_target()
    for layer, shape in RESNET50.items():
        stride = (shape.sh, shape.sw)
        try:
            x, w = conv_operands(shape, seed)
        except Exception:
            ph.crash(f"{layer} operands")
            continue
        for backend in ("pallas", "im2col"):
            label = f"{layer}/{backend}"
            try:
                ctx = ops.ExecutionContext(target=target, backend=backend)
                compiled, c_secs, log = compile_recorded(
                    functools.partial(ops.conv2d, stride=stride, ctx=ctx),
                    x, w)
                t0 = time.perf_counter()
                out = jax.block_until_ready(compiled(x, w))
                r_secs = time.perf_counter() - t0
                err, mag = (float(v) for v in conv_error(out, x, w, stride))
                del out
                convs = [d for d in log if d.op == "conv2d"]
                lowered = "tpu_custom_call" in compiled.as_text()
                routed = bool(convs) and all(
                    d.chosen == d.requested == backend and not d.degraded
                    and d.plan_source == "analytic" for d in convs)
                ph.check(label, lowered and routed and err <= CONV_RTOL * mag,
                         f"tpu_custom_call={lowered} dispatch={routed} "
                         f"({[(d.requested, d.chosen, d.degraded) for d in convs]}) "
                         f"max|err|={err:.3e} max|ref|={mag:.3e} "
                         f"(tol {CONV_RTOL:g}*max|ref|) compile={c_secs:.2f}s "
                         f"smoke wall time of one call={r_secs:.4f}s "
                         f"peak={peak_gib(dev)}")
            except Exception:
                ph.crash(label)
        del x, w
    return ph


# ---------------------------------------------------------------------------
# Phase serve
# ---------------------------------------------------------------------------

def run_serve_phase(dev, seed: int) -> Phase:
    from repro import ops
    from repro.launch.serve import build_engine, make_requests, summarize
    from repro.models import transformer as T

    ph = Phase("serve")
    try:
        t0 = time.perf_counter()
        cfg, eng = build_engine(SERVE_ARCH, max_len=SERVE_MAX_LEN, seed=seed)
        jax.block_until_ready(eng.params)
        n_params = sum(p.size for p in jax.tree_util.tree_leaves(eng.params))
        say(f"[serve] built {cfg.name}: {n_params / 1e9:.3f} B params in "
            f"{cfg.param_dtype}, paged={eng.paged} batch={eng.batch_size} "
            f"blocks={getattr(eng, 'num_blocks', None)} "
            f"backend={eng.ctx.backend} in {time.perf_counter() - t0:.1f}s "
            f"peak={peak_gib(dev)}")
        reqs = make_requests(cfg, SERVE_REQUESTS, SERVE_PROMPT_LENS,
                             SERVE_NEW_TOKENS, seed=seed)
        with ops.record_dispatch() as log:
            t0 = time.perf_counter()
            eng.serve(reqs)
            secs = time.perf_counter() - t0
        total_new, reasons = summarize(reqs)
        done = all(r.finish_reason in ("stop", "length") for r in reqs)
        ph.check("requests", done and eng.paged,
                 f"{len(reqs)} requests, prompts "
                 f"{[len(r.prompt) for r in reqs]}, finish={reasons}, "
                 f"{total_new} tokens generated; smoke wall time "
                 f"{secs:.1f}s, compilation included; peak={peak_gib(dev)}")
        counts = collections.Counter((d.op, d.chosen) for d in log)
        say("[serve] dispatch decisions made while tracing, by (op, chosen "
            "backend): " + ", ".join(f"{op}->{b}: {n}" for (op, b), n
                                     in sorted(counts.items())))
    except Exception:
        ph.crash("serve")
        return ph
    try:
        tokens = jnp.asarray(reqs[0].prompt)[None]
        logits = {}
        for backend in ("pallas", "xla"):
            ctx = ops.ExecutionContext(target=eng.target, backend=backend)
            fwd = jax.jit(lambda p, t, ctx=ctx: T.forward(p, cfg, tokens=t,
                                                          ctx=ctx)[0])
            logits[backend] = fwd(eng.params, tokens)
        diff = float(jnp.max(jnp.abs(logits["pallas"] - logits["xla"])))
        mag = float(jnp.max(jnp.abs(logits["xla"])))
        agree = float(jnp.mean(jnp.argmax(logits["pallas"], -1)
                               == jnp.argmax(logits["xla"], -1)))
        finite = bool(jnp.all(jnp.isfinite(logits["pallas"])))
        ph.check("prefill logits pallas vs xla",
                 finite and diff <= LOGIT_RTOL * mag,
                 f"{tokens.shape[1]} tokens, max|diff|={diff:.4e} "
                 f"max|logit|={mag:.4e} (tol {LOGIT_RTOL:g}*max|logit|), "
                 f"argmax agreement={agree:.4f}")
    except Exception:
        ph.crash("prefill logits")
    return ph


# ---------------------------------------------------------------------------
# Phase dist (four chips)
# ---------------------------------------------------------------------------

def run_dist_phase(dev, seed: int, chips: int) -> Phase:
    from repro import distributed, ops
    from repro.configs.resnet50_convs import RESNET50
    from repro.core.parallel_tiling import ParallelBlocking
    from repro.launch import make_conv_mesh
    from repro.plan import default_target

    ph = Phase("dist")
    shape = RESNET50[DIST_LAYER]
    stride = (shape.sh, shape.sw)
    ctx = ops.ExecutionContext(target=default_target(), backend="pallas")
    try:
        x, w = conv_operands(shape, seed)
        ref = jax.jit(functools.partial(ops.conv2d, stride=stride,
                                        ctx=ctx))(x, w)
        grids = {"lp": distributed.default_blocking(
                     x.shape, w.shape, stride, P_devices=chips),
                 "probe": ParallelBlocking.from_grid(shape, DIST_PROBE_GRID)}
    except Exception:
        ph.crash("setup")
        return ph
    mag = float(jnp.max(jnp.abs(ref)))
    for name, pb in grids.items():
        grid = {k: v for k, v in pb.grid.items() if v > 1}
        try:
            mesh = make_conv_mesh(pb)
            xs, ws = jax.device_put((x, w), NamedSharding(mesh, P()))
            for kind, fn in (("halo", ops.conv2d_dist),
                             ("allgather", distributed.allgather_conv)):
                f = functools.partial(fn, stride=stride, blocking=pb,
                                      mesh=mesh, ctx=ctx)
                compiled, c_secs, log = compile_recorded(f, xs, ws)
                t0 = time.perf_counter()
                out = jax.block_until_ready(compiled(xs, ws))
                r_secs = time.perf_counter() - t0
                err = float(jnp.max(jnp.abs(
                    jax.device_put(out, ref.sharding) - ref)))
                del out
                lowered = "tpu_custom_call" in compiled.as_text()
                local = [d for d in log if d.op == "conv2d"]
                routed = bool(local) and all(
                    d.chosen == "pallas" and not d.degraded for d in local)
                ph.check(f"{name} {grid} {kind} vs single-device",
                         lowered and routed and err <= CONV_RTOL * mag,
                         f"tpu_custom_call={lowered} local pallas={routed} "
                         f"max|err|={err:.3e} max|ref|={mag:.3e} "
                         f"(tol {CONV_RTOL:g}*max|ref|) compile={c_secs:.2f}s "
                         f"smoke wall time of one call={r_secs:.4f}s")
        except Exception:
            ph.crash(f"{name} {grid}")
    say(f"[dist] peak on device 0: {peak_gib(dev)}")
    return ph


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed conv on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        say(f"chip_smoke: FAIL: no repro package under {src}; run from a "
            "checkout of the repository")
        return 2
    sys.path.insert(0, src)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        say(f"chip_smoke: FAIL: no TPU found (JAX's first device is "
            f"{dev.platform!r}: {dev.device_kind}); this script never falls "
            "back to the CPU")
        return 2
    if len(devices) < args.chips:
        say(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
            f"{len(devices)} device(s)")
        return 2

    from repro.launch import use_compile_cache

    cache_dir = use_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update(
            [event.rsplit("/", 1)[-1]]
            if event.startswith("/jax/compilation_cache/cache_") else []))
    say(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"using {args.chips}; compile cache at {cache_dir}")

    t_start = time.perf_counter()
    if args.chips == 1:
        phases = [run_conv_phase(dev, args.seed),
                  run_serve_phase(dev, args.seed)]
    else:
        phases = [run_dist_phase(dev, args.seed, args.chips)]
    say(f"chip_smoke: compile cache hits={cache_events['cache_hits']} "
        f"misses={cache_events['cache_misses']}; total smoke wall time "
        f"{time.perf_counter() - t_start:.1f}s")
    for ph in phases:
        say(f"chip_smoke: phase {ph.name}: "
            f"{'PASS' if ph.ok else 'FAIL ' + ', '.join(ph.failures)}")
    if not all(ph.ok for ph in phases):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
