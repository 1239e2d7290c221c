"""Offline LFM2-MoE serving cells: ``kinds/lm_serve.py``'s jobs, window,
sample and check, with LFM2's configuration, weights and reference.

This directory (``benchmarks/chip/lfm2/``) is a benchmark directory of its
own: ``spec.resolve`` finds a configuration's kind, traffic and metric
readers beside its file, so this module is the ``lm_serve`` kind of the
configurations here, and the metric readers here load the shared ones.

The configuration (Hugging Face ``config.json`` keys of ``lfm2_moe``, with
``deployment`` stating the expert-parallel share this chip holds) becomes
the program's ``ModelConfig``: the published layer order of short convs
and attentions, leading dense layers, QK-norm, sigmoid-plus-bias routing
over all the router's experts and a dropless expert layer that computes
the held ones. The weights come from the seed through the reference's own
generator (``reference/lfm2_moe.py``), one jitted call per layer kind. The
engine is built as a user builds it for this deployment: the default
target and backend, the paged pool planned by the engine for the mix's
``max_len``, and ``slots`` rows, which the traffic file sets so that each
held expert sees the tokens it would in the deployment.

Set-up warms every program the mix reaches through ``Engine.serve`` alone:
one two-token request per prefill bucket (its prefill, its cache insert,
the decode step and the sampler). The decode step runs at the full table
width, so no longer request has a program left to compile.

Jobs, window and sample are ``kinds/lm_serve.py``'s. The check compares
the served tokens of the sample with the float32 reference's logits by two
numbers. ``mean_gap``, the mean over served tokens of the gap by which a
served token's reference logit lies below the reference's best, tells
bfloat16 from the fp8 control: it averages over every served token, so the
rare router pick that one bfloat16 rounding flips moves it little.
``logit_gap``, the widest such gap, is a loose bound on any single token:
a flipped pick can move one token's logits by several tenths. The traced
job also carries its FLOPs from ``work_lfm2.job_flops``, with the expert
FLOPs taken from the engine's ``moe_held`` counter.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import spec
from benchmarks.chip import work_lfm2 as work
from benchmarks.chip.reference import lfm2_moe as ref
from benchmarks.chip import traffic as gen

lm_serve = spec.load_module(os.path.join(spec.HERE, "kinds", "lm_serve.py"))


def model_config(config: dict):
    """The program's ``ModelConfig`` for an ``lfm2_moe`` configuration."""
    from repro.models.config import ModelConfig

    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True), ("routed_scaling_factor", 1)):
        if config[key] != want:
            raise ValueError(f"only {key}={want} is modelled")
    d = ref.dims_of(config)
    return ModelConfig(
        name=config.get("name", "lm"), family="hybrid",
        n_layers=d["num_hidden_layers"], d_model=d["hidden_size"],
        n_heads=d["num_attention_heads"], n_kv_heads=d["num_key_value_heads"],
        d_ff=d["intermediate_size"], moe_d_ff=d["moe_intermediate_size"],
        vocab_size=d["vocab_size"], pattern=d["layer_types"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]), qk_norm=True,
        conv_kernel=d["conv_L_cache"], n_experts=d["router_experts"],
        experts_per_token=d["num_experts_per_tok"],
        n_dense_layers=d["num_dense_layers"], router="sigmoid_bias",
        moe_dropless=True, expert_shards=d["router_experts"] // d["held"],
        expert_shard=d["held_first"] // d["held"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"])


@functools.partial(jax.jit, static_argnames=("frozen", "kinds"))
def _block(key, layer, *, frozen, kinds):
    """One layer of the program's parameter tree, from the reference's
    generator (expert layers: the router and its bias in float32)."""
    w = ref.layer_weights(key, dict(frozen), layer, kinds)
    core = ("wq", "wk", "wv", "wo", "q_norm", "k_norm") if kinds[0] == "attn" \
        else ("w_in", "conv_w", "w_out")
    if kinds[1] == "dense":
        ffn = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    else:
        ffn = {"router": w["router"].astype(jnp.float32),
               "expert_bias": w["expert_bias"].astype(jnp.float32),
               "w_gate": w["e_gate"], "w_up": w["e_up"], "w_down": w["e_down"]}
    blk = {"norm1": w["op_norm"], "core": {k: w[k] for k in core},
           "norm2": w["ffn_norm"], "ffn": ffn}
    return jax.tree.map(lambda a: a[None], blk)  # one pattern repeat


@functools.partial(jax.jit, static_argnames=("frozen", "padded_vocab"))
def _globals(key, *, frozen, padded_vocab):
    glob = ref.global_weights(key, dict(frozen))
    embed = jnp.pad(glob["embed"],
                    ((0, padded_vocab - dict(frozen)["vocab_size"]), (0, 0)))
    return {"embed": embed, "final_norm": glob["final_norm"],
            "head": embed.T}


def program_weights(config: dict, cfg, seed: int):
    """Weights on the device, checked leaf by leaf against the shapes and
    types the program's own initializer gives. The LM head holds the
    embedding's transpose (tied), zero past the vocabulary."""
    from repro.models import transformer as T

    if not config.get("tie_word_embeddings", False):
        raise ValueError("the weight generator ties the LM head")
    dims = ref.dims_of(config)
    frozen = tuple(sorted(dims.items()))
    key = gen.seed_key(seed)
    params = _globals(key, frozen=frozen, padded_vocab=cfg.padded_vocab)
    params["layers"] = {
        f"b{i}": _block(key, jnp.int32(i), frozen=frozen,
                        kinds=ref.layer_kind(dims, i))
        for i in range(dims["num_hidden_layers"])}
    want = jax.eval_shape(lambda k: T.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's parameter "
                         f"tree:\n got {got}\n want {exp}")
    return params


class System(lm_serve.System):
    def __init__(self, cell, seed: int, say):
        super().__init__(cell, seed, say)
        self.dims = ref.dims_of(self.config)
        self.mean_limit = float(self.config["check"]["mean_gap_limit"])

    def setup(self) -> None:
        from repro.serving.engine import Engine

        cfg = model_config(self.config)
        t0 = time.perf_counter()
        params = program_weights(self.config, cfg, self.seed)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.engine = Engine(cfg, params, max_len=int(self.mix["max_len"]),
                             batch_size=int(self.mix["slots"]))
        eng = self.engine
        self.say(f"engine: weights {t1 - t0:.3f} s, slots {eng.batch_size}, "
                 f"paged {eng.paged}, blocks "
                 f"{getattr(eng, 'num_blocks', None)}"
                 f", backend {eng.ctx.backend}, prefill bucket "
                 f"{eng.prefill_bucket}, experts held "
                 f"{cfg.experts_held} of {cfg.n_experts}")
        self._warm()
        self.say(f"engine: warm-up {time.perf_counter() - t1:.3f} s")

    def _warm(self) -> None:
        """One two-token request per prefill bucket the mix reaches, at the
        bucket's top length, and one at the shortest prompt."""
        from repro import ops

        eng, V = self.engine, self.dims["vocab_size"]
        bucket = eng.prefill_bucket
        lo = gen.shortest_prompt(self.mix)
        hi, _ = gen.longest(self.mix)
        rng = np.random.default_rng(0)
        lengths = sorted({lo} | {min(hi, b) for b in range(
            -(-lo // bucket) * bucket, hi + bucket, bucket)})
        with ops.record_dispatch() as log:
            for n in lengths:
                eng.serve([self._request(rng.integers(0, V, n, dtype=np.int32),
                                         2)])
        seen = sorted({(d.op, d.requested, d.chosen, d.degraded) for d in log})
        self.say("dispatch while tracing (op, requested, chosen, degraded): "
                 + "; ".join(map(str, seen)))

    def traced(self) -> dict:
        reqs, dt = self._job(0)
        self.done.extend(reqs)
        served = [(len(r.prompt), len(r.out_tokens)) for r in reqs]
        held = sum(self.engine.stats.moe_held.values())
        return {"attempted": len(reqs), "failed": self._failed(reqs),
                "job_s": dt, "served": served,
                "prompt_tokens": sum(p for p, _ in served),
                "job_flops": work.job_flops(self.dims, served, held)}

    def _gaps(self, control: bool):
        sample = self._sample()
        V = self.dims["vocab_size"]
        bad = sum(int(np.sum((s < 0) | (s >= V))) for _, s in sample)
        sample = [(p, np.clip(s, 0, V - 1)) for p, s in sample]
        t0 = time.perf_counter()
        out = ref.served_gaps(self.config, self.seed, sample,
                              length=self._ref_length(), control=control,
                              group_tokens=lm_serve.REF_GROUP_TOKENS)
        n = sum(len(s) for _, s in sample)
        self.say(f"reference: {len(sample)} requests, {n} served tokens, "
                 f"{time.perf_counter() - t0:.3f} s; widest gap "
                 f"{float(out['gap'].max())!r}, mean gap "
                 f"{float(out['gap'].mean())!r}, share of tokens the "
                 f"reference also ranks first "
                 f"{float(np.mean(out['gap'] == 0))!r}; router picks one "
                 f"bfloat16 rounding moves: {out['flips']} of "
                 f"{out['picks']} tokens routed")
        return bad, out

    def check(self):
        bad, out = self._gaps(control=False)
        return [("mean_gap", float(out["gap"].mean()), self.mean_limit),
                ("logit_gap", float(out["gap"].max()), self.limit),
                ("out_of_vocab", float(bad), 0.0)]

    def control(self):
        _, out = self._gaps(control=True)
        return [("mean_gap", float(out["control_gap"].mean())),
                ("logit_gap", float(out["control_gap"].max()))]
