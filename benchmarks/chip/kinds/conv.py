"""Conv cells: a configuration's conv layers through ``repro.ops.conv2d``.

A pass calls each layer the traffic names once, at the configuration's
batch, through ``ops.conv2d`` with the default execution context (the
attached device's target and backend), back to back, and waits for the
pass once. Operands are made on the device from the seed in one call.
``pass_ms`` is the window's time over the passes it completed. The check
compares every output of the window's last pass with the float32
reference.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.chip import work
from benchmarks.chip.traffic import seed_key
from benchmarks.chip.reference import conv as ref

TRACED_PASSES = 5


@functools.partial(jax.jit, static_argnames=("shapes",))
def _operands(key, *, shapes):
    """(x, w) per layer: x ~ N(0, 1), w ~ N(0, 1/fan_in), in bfloat16."""
    out = []
    for i, (n, c_i, c_o, h, w_, h_f, w_f) in enumerate(shapes):
        kx, kw = jax.random.split(jax.random.fold_in(key, i))
        x = jax.random.normal(kx, (n, c_i, h, w_), jnp.float32)
        w = jax.random.normal(kw, (c_o, c_i, h_f, w_f), jnp.float32)
        out.append((x.astype(jnp.bfloat16),
                    (w * (c_i * h_f * w_f) ** -0.5).astype(jnp.bfloat16)))
    return out


class System:
    def __init__(self, cell, seed: int, say):
        self.say, self.seed = say, seed
        cfg = cell.config
        if cfg["dtype"] != "bfloat16" or cfg["padding"] != "VALID":
            raise ValueError("conv cells run bfloat16 operands, VALID padding")
        self.batch, self.out_dtype = int(cfg["batch"]), cfg["out_dtype"]
        self.names = list(cell.traffic["layers"])
        self.layers = [cfg["layers"][n] for n in self.names]
        self.limit = float(cfg["check"]["rel_err_limit"])
        self.outs = None

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        from repro import ops

        shapes = tuple((self.batch, l["c_I"], l["c_O"], *work.conv_input_hw(l),
                        l["h_F"], l["w_F"]) for l in self.layers)
        self.operands = _operands(seed_key(self.seed), shapes=shapes)
        self.calls = [jax.jit(functools.partial(
            ops.conv2d, stride=(l["stride"], l["stride"])))
            for l in self.layers]
        with ops.record_dispatch() as log:
            self.one_pass()
        self.decisions = [d for d in log if d.op == "conv2d"]
        for name, d in zip(self.names, self.decisions):
            self.say(f"dispatch {name}: requested={d.requested} "
                     f"chosen={d.chosen} degraded={d.degraded} "
                     f"measured_words={d.measured_words} "
                     f"lower_bound={d.lower_bound}")
        dtype = str(self.outs[0].dtype)
        if dtype != self.out_dtype:
            raise ValueError(f"conv output is {dtype}, the configuration "
                             f"states {self.out_dtype}")
        self.one_pass()

    def one_pass(self) -> None:
        self.outs = None  # free the last pass's outputs before the next
        outs = []
        with jax.profiler.TraceAnnotation("bench/pass"):
            for name, call, (x, w) in zip(self.names, self.calls,
                                          self.operands):
                with jax.profiler.TraceAnnotation(f"bench/layer/{name}"):
                    outs.append(call(x, w))
            jax.block_until_ready(outs)
        self.outs = outs

    # -- measurement ----------------------------------------------------------
    def window(self, seconds: float) -> dict:
        passes, t0 = 0, time.perf_counter()
        ends = []
        while True:
            self.one_pass()
            passes += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
        # single passes, to tell a slow stretch from a uniformly slow run
        each = sorted(b - a for a, b in zip([0.0] + ends, ends))
        self.say(f"window: {passes} passes in {elapsed:.4f} s; pass ms "
                 f"min {1e3 * each[0]:.3f} median "
                 f"{1e3 * each[len(each) // 2]:.3f} max {1e3 * each[-1]:.3f}")
        return {"metrics": {"pass_ms": 1e3 * elapsed / passes},
                "attempted": passes * len(self.layers), "failed": 0}

    def traced(self) -> dict:
        t0 = time.perf_counter()
        for _ in range(TRACED_PASSES):
            self.one_pass()
        elapsed = time.perf_counter() - t0
        work_per_layer = [
            (name, work.conv_flops(self.batch, l),
             work.conv_bytes(self.batch, l, "bfloat16", self.out_dtype))
            for name, l in zip(self.names, self.layers)]
        return {"attempted": TRACED_PASSES * len(self.layers), "failed": 0,
                "passes": TRACED_PASSES, "elapsed_s": elapsed,
                "layers": work_per_layer, "decisions": self.decisions}

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        self.calls = None

    def _errors(self, control: bool):
        out = []
        for name, l, (x, w), y in zip(self.names, self.layers, self.operands,
                                      self.outs):
            if control:
                err, mag = ref.control_error(x, w, stride=l["stride"])
            else:
                err, mag = ref.error(y, x, w, stride=l["stride"])
            err, mag = float(err), float(mag)
            self.say(f"{'control' if control else 'program'} {name}: "
                     f"max|err| {err!r} max|ref| {mag!r}")
            out.append(err / mag if mag > 0 else float("inf"))
        return max(out)

    def check(self):
        return [("rel_err", self._errors(control=False), self.limit)]

    def control(self):
        return [("rel_err", self._errors(control=True))]

