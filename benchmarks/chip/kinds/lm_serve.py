"""Offline LM serving cells: jobs of requests through ``repro.serving.Engine``.

The configuration (Hugging Face ``config.json`` keys) becomes the program's
``ModelConfig``; the weights come from the seed, made on the device in one
call by the reference's own generator (``reference/qwen2.py``), so the
reference can regenerate them layer by layer. The engine is built as a user
builds it: the default target and backend, the slot pool and the paged KV
pool planned by the engine for the mix's ``max_len``.

A job is one ``Engine.serve`` call over ``job_requests`` greedy requests,
each run to its ``max_new_tokens`` (no stop tokens, so a seed never changes
the amount of work). Jobs run back to back until the window has passed;
the last job runs to its end. ``serve_tok_s`` is prompt plus generated
tokens of every job over the time of all the jobs.

Set-up warms every program the mix can reach, through ``Engine.serve``
alone: one single-request job per prefill bucket (its prefill, its cache
insert and the decode width of its length), then the mix's longest request,
which walks the decode widths above the longest prompt.

The check reads a sample of finished requests drawn from the seed, with the
longest among them: the reference runs over each prompt and its served
tokens, and the widest gap by which a served token's reference logit lies
below the reference's best is compared with the configuration's limit.
"""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic as gen
from benchmarks.chip import work
from benchmarks.chip.reference import qwen2 as ref

# reference rows of this many positions at a time (rows x max_len)
REF_GROUP_TOKENS = 8192


def model_config(config: dict):
    """The program's ``ModelConfig`` for a Qwen2 configuration."""
    from repro.models.config import ModelConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) MLPs are modelled")
    return ModelConfig(
        name=config.get("name", "lm"), family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qkv_bias=True, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), pattern=("attn",),
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


@functools.partial(jax.jit, static_argnames=("dims", "padded_vocab"))
def _program_weights(key, *, dims, padded_vocab):
    """The program's parameter tree, from the reference's generator. The LM
    head holds the embedding's transpose (tied), zero past the vocabulary."""
    d = dict(dims)
    lay = jax.lax.map(lambda i: ref.layer_weights(key, d, i),
                      jnp.arange(d["num_hidden_layers"]))
    glob = ref.global_weights(key, d)
    embed = jnp.pad(glob["embed"],
                    ((0, padded_vocab - d["vocab_size"]), (0, 0)))
    return {
        "embed": embed,
        "layers": {"b0": {
            "norm1": lay["attn_norm"],
            "core": {k: lay[k] for k in ("wq", "wk", "wv", "wo",
                                         "bq", "bk", "bv")},
            "norm2": lay["mlp_norm"],
            "ffn": {k: lay[k] for k in ("w_gate", "w_up", "w_down")},
        }},
        "final_norm": glob["final_norm"],
        "head": embed.T,
    }


def program_weights(config: dict, cfg, seed: int):
    """Weights on the device, checked leaf by leaf against the shapes and
    types the program's own initializer gives."""
    from repro.models import transformer as T

    if not config.get("tie_word_embeddings", False):
        raise ValueError("the weight generator ties the LM head")
    dims = tuple(sorted(ref.dims_of(config).items()))
    params = _program_weights(gen.seed_key(seed), dims=dims,
                              padded_vocab=cfg.padded_vocab)
    want = jax.eval_shape(lambda k: T.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's parameter "
                         f"tree:\n got {got}\n want {exp}")
    return params


class System:
    def __init__(self, cell, seed: int, say):
        self.say, self.seed = say, seed
        self.config, self.mix = cell.config, cell.traffic
        if self.mix["kind"] != "offline_jobs":
            raise ValueError(f"lm_serve cells take offline_jobs traffic, "
                             f"not {self.mix['kind']!r}")
        self.dims = ref.dims_of(self.config)
        self.limit = float(self.config["check"]["logit_gap_limit"])
        self.done = []  # every request the window finished

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        from repro.serving.engine import Engine

        cfg = model_config(self.config)
        t0 = time.perf_counter()
        params = program_weights(self.config, cfg, self.seed)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.engine = Engine(cfg, params, max_len=int(self.mix["max_len"]))
        eng = self.engine
        self.say(f"engine: weights {t1 - t0:.3f} s, slots {eng.batch_size}, "
                 f"paged {eng.paged}, blocks {getattr(eng, 'num_blocks', None)}"
                 f", backend {eng.ctx.backend}, prefill bucket "
                 f"{eng.prefill_bucket}")
        self._warm()
        self.say(f"engine: warm-up {time.perf_counter() - t1:.3f} s")

    def _request(self, prompt, max_new):
        from repro.serving.engine import Request

        return Request(prompt=prompt, max_new_tokens=int(max_new))

    def _warm(self) -> None:
        """Single-request jobs: one per prefill bucket the mix reaches (at
        the bucket's top length, so also the decode width there) and one at
        the shortest prompt (the narrowest width); then the mix's longest
        request, which walks every width above the longest prompt."""
        from repro import ops

        eng, V = self.engine, self.dims["vocab_size"]
        bucket = eng.prefill_bucket
        lo = gen.shortest_prompt(self.mix)
        hi, out_hi = gen.longest(self.mix)
        rng = np.random.default_rng(0)
        lengths = sorted({lo} | {min(hi, b) for b in range(
            -(-lo // bucket) * bucket, hi + bucket, bucket)})
        with ops.record_dispatch() as log:
            for n in lengths:
                eng.serve([self._request(rng.integers(0, V, n, dtype=np.int32),
                                         2)])
            eng.serve([self._request(rng.integers(0, V, hi, dtype=np.int32),
                                     out_hi)])
        seen = sorted({(d.op, d.requested, d.chosen, d.degraded) for d in log})
        self.say("dispatch while tracing (op, requested, chosen, degraded): "
                 + "; ".join(map(str, seen)))

    # -- measurement ----------------------------------------------------------
    def _job(self, index: int):
        reqs = [self._request(p, o) for p, o in
                gen.job(self.mix, self.seed, index, self.dims["vocab_size"])]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/job"):
            self.engine.serve(reqs)
        return reqs, time.perf_counter() - t0

    @staticmethod
    def _tokens(reqs) -> int:
        return sum(len(r.prompt) + len(r.out_tokens) for r in reqs)

    def _failed(self, reqs) -> int:
        return sum(r.finish_reason != "length"
                   or len(r.out_tokens) != r.max_new_tokens for r in reqs)

    def window(self, seconds: float) -> dict:
        busy, tokens, jobs, each = 0.0, 0, 0, []
        while busy < seconds:
            reqs, dt = self._job(jobs)
            busy += dt
            tokens += self._tokens(reqs)
            jobs += 1
            each.append(round(dt, 4))
            self.done.extend(reqs)
        self.say(f"window: {jobs} jobs, {len(self.done)} requests, {tokens} "
                 f"tokens in {busy:.4f} s; job s {each}")
        return {"metrics": {"serve_tok_s": tokens / busy},
                "attempted": len(self.done), "failed": self._failed(self.done)}

    def traced(self) -> dict:
        reqs, dt = self._job(0)
        self.done.extend(reqs)
        served = [(len(r.prompt), len(r.out_tokens)) for r in reqs]
        return {"attempted": len(reqs), "failed": self._failed(reqs),
                "job_s": dt, "served": served,
                "prompt_tokens": sum(p for p, _ in served),
                "job_flops": work.lm_job_flops(self.dims, served)}

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        self.engine = None
        gc.collect()

    def _sample(self):
        """Finished requests to check, ``check_requests`` in all: the
        longest, and one drawn from the seed in each of equal stretches of
        the finished list, which is in the order the requests were handed to
        the server; so the sample spans every job, and slots filled early
        and late."""
        done = [r for r in self.done if r.out_tokens is not None
                and len(r.out_tokens) > 0]
        longest = max(range(len(done)),
                      key=lambda i: len(done[i].prompt) + len(done[i].out_tokens))
        rest = [i for i in range(len(done)) if i != longest]
        parts = min(len(rest), int(self.mix["check_requests"]) - 1)
        rng = np.random.default_rng([self.seed, 1])
        pick = [longest] + [int(rng.choice(part)) for part in
                            np.array_split(rest, parts)] if parts else [longest]
        return [(np.asarray(done[i].prompt), np.asarray(done[i].out_tokens))
                for i in pick]

    def _ref_length(self) -> int:
        hi, out_hi = gen.longest(self.mix)
        return -(-(hi + out_hi - 1) // 128) * 128

    def _gaps(self, control: bool):
        sample = self._sample()
        V = self.dims["vocab_size"]
        bad = sum(int(np.sum((s < 0) | (s >= V))) for _, s in sample)
        sample = [(p, np.clip(s, 0, V - 1)) for p, s in sample]
        t0 = time.perf_counter()
        out = ref.served_gaps(self.config, self.seed, sample,
                              length=self._ref_length(), control=control,
                              group_tokens=REF_GROUP_TOKENS)
        n = sum(len(s) for _, s in sample)
        first = np.array([g[0] for g in np.split(out["gap"], np.cumsum(
            [len(s) for _, s in sample])[:-1])])
        self.say(f"reference: {len(sample)} requests, {n} served tokens, "
                 f"{time.perf_counter() - t0:.3f} s; widest gap "
                 f"{float(out['gap'].max())!r} (first tokens "
                 f"{float(first.max())!r}), share of tokens the reference "
                 f"also ranks first {float(np.mean(out['gap'] == 0))!r}")
        return bad, out

    def check(self):
        bad, out = self._gaps(control=False)
        return [("logit_gap", float(out["gap"].max()), self.limit),
                ("out_of_vocab", float(bad), 0.0)]

    def control(self):
        _, out = self._gaps(control=True)
        return [("logit_gap", float(out["control_gap"].max()))]
