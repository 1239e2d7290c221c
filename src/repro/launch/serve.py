"""Serving launcher: continuous-batching decode against a (smoke or
checkpointed) model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --smoke \
        --requests 8 --prompt-len 16 --max-new 32

``--batch 0`` sizes the slot pool from the hardware target's memory model.
Reported tok/s counts only tokens that were actually generated (EOS /
cache-limit truncation shortens ``out_tokens``; nothing is zero-padded).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import logging
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np


def build_engine(arch: str, *, smoke: bool = False,
                 ckpt_dir: Optional[str] = None, max_len: int = 256,
                 batch: Optional[int] = 4,
                 target: Optional[str] = None, backend: Optional[str] = None,
                 seed: int = 0):
    """``(cfg, engine)`` for ``arch``: the smoke config, or the published
    config with its weights built (or restored) in ``cfg.compute_dtype`` —
    decode never needs f32 master weights, and a 3B model in f32 would not
    fit beside its KV pool on one 16 GB chip. Weights are random from
    ``seed`` unless ``ckpt_dir`` is given. ``target`` names a preset;
    ``None`` takes the attached device's."""
    from repro.configs import get_config, get_smoke
    from repro.models import transformer as T
    from repro.ops import ExecutionContext
    from repro.plan import default_target, get_target
    from repro.serving.engine import Engine
    from repro.train import checkpoint as ckpt

    cfg = get_smoke(arch) if smoke else get_config(arch)
    if not smoke:
        cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    assert cfg.causal, "encoder-only archs have no decode path"
    params = jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    if ckpt_dir:
        restored, _ = ckpt.restore(ckpt_dir, {"params": params})
        params = restored["params"]
    tgt = get_target(target) if target else default_target()
    eng = Engine(cfg, params, max_len=max_len, batch_size=batch or None,
                 target=tgt, ctx=ExecutionContext(target=tgt, backend=backend))
    return cfg, eng


def make_requests(cfg, n: int, prompt_lens: Tuple[int, int], max_new: int,
                  temperature: float = 0.0, stop: Sequence[int] = (),
                  deadline_s: Optional[float] = None, seed: int = 0):
    """``n`` requests with seeded random prompts whose lengths are drawn
    uniformly from ``prompt_lens`` (inclusive)."""
    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(lo, hi + 1)),
                                        dtype=np.int32),
                    max_new_tokens=max_new, temperature=temperature,
                    stop_tokens=tuple(stop), deadline_s=deadline_s)
            for _ in range(n)]


def summarize(reqs: List) -> Tuple[int, dict]:
    """(generated tokens, finish-reason counts) — only tokens that were
    really generated count; "error" and "timeout" requests still return
    what they produced."""
    total_new = sum(len(r.out_tokens) for r in reqs
                    if r.out_tokens is not None)
    return total_new, dict(collections.Counter(r.finish_reason
                                               for r in reqs))


def latency_summary(reqs: List) -> dict:
    """p50 and p95 of the time to first token and of the time per output
    token after it, ``(t_done - t_first) / (tokens - 1)``, in ms over the
    served requests (requests with fewer than two tokens have no
    inter-token time)."""
    ttft = [r.t_first for r in reqs if r.t_first is not None]
    itl = [(r.t_done - r.t_first) / (len(r.out_tokens) - 1) for r in reqs
           if r.t_first is not None and len(r.out_tokens) > 1]
    out = {}
    for name, xs in (("ttft", ttft), ("itl", itl)):
        if xs:
            out[f"{name}_p50_ms"], out[f"{name}_p95_ms"] = (
                1e3 * float(q) for q in np.percentile(xs, [50, 95]))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="slot-pool size; 0 = plan from the hardware target")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--stop-token", type=int, default=None,
                    help="token id that ends a request (EOS)")
    ap.add_argument("--target", default=None,
                    help="hardware target preset (tpu_v5e | gemmini | "
                         "cpu_interpret); sets plan/precision policy and "
                         "the default backend. Default: the attached "
                         "device's preset")
    ap.add_argument("--backend", choices=("xla", "pallas"), default=None,
                    help="kernel backend override; default resolves from "
                         "REPRO_BACKEND and then the --target preset")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget from admission; "
                         "expired requests finish with reason 'timeout'")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    from repro.launch.devices import use_compile_cache

    use_compile_cache()
    cfg, eng = build_engine(args.arch, smoke=args.smoke,
                            ckpt_dir=args.ckpt_dir, max_len=args.max_len,
                            batch=args.batch,
                            target=args.target, backend=args.backend)
    stop = () if args.stop_token is None else (args.stop_token,)
    reqs = make_requests(cfg, args.requests, (2, args.prompt_len),
                         args.max_new, args.temperature, stop,
                         args.deadline_s)
    t0 = time.time()
    eng.serve(reqs)
    dt = time.time() - t0
    total_new, reasons = summarize(reqs)
    ok = len(reqs) - reasons.get("error", 0) - reasons.get("timeout", 0)
    print(f"served {len(reqs)} requests "
          f"(batch={eng.batch_size}), {total_new} generated tokens in "
          f"{dt:.2f}s ({total_new / dt:.1f} tok/s); "
          f"completed={ok}/{len(reqs)} finish={reasons}")
    stats = dataclasses.asdict(eng.stats)
    stats["failures"] = dict(stats["failures"])
    print(f"engine stats: {stats}")
    print(f"latency: {latency_summary(reqs)}")
    for i, r in enumerate(reqs[:4]):
        out = [] if r.out_tokens is None else r.out_tokens[:12].tolist()
        print(f"  req{i}: prompt={r.prompt[:8].tolist()}... "
              f"out={out} ({r.finish_reason})")


if __name__ == "__main__":
    main()
