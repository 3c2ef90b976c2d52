"""Closed-loop prefill: fresh batches back to back through the program's
jitted prefill step (``launch.steps.build_step`` on a one-device mesh).

The mix (``"kind": "prefill"``) sends ``batch`` prompts of ``seq_len``
tokens a step, drawn fresh for every step from the seed and the step's
index by the mix's token distribution.

Set-up draws the weights and warms the one step shape; the window then
keeps about ``LEAD_S`` seconds of steps in flight ahead of the one the host
waits on, so that the device does not wait for the host or the runtime to
enqueue: a pause of ≈ 2.5 s, while one step was in flight, idled the chip
in some runs on the chip.  After the window a sample of
the sequences it processed, drawn from the seed, is checked against the
plain reference.
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import time

import numpy as np

WARM_STEP = 1 << 30           # token stream index of the warm-up batches
LEAD_S = 3.0                  # seconds of steps kept in flight


def validate(mix: dict) -> None:
    for key in ("batch", "seq_len"):
        if not isinstance(mix.get(key), int) or mix[key] < 1:
            raise ValueError(f"prefill mix needs a positive {key}: {mix}")


def tokens_fn(mix: dict, vocab: int, seed: int):
    """``step -> tokens [batch, seq_len] int32`` for this seed, on the
    device; one compiled program serves every seed."""
    from bench import traffic
    from bench.weights import seed_key

    dist, params = traffic.tokens(mix)
    draw = _draw(dist, json.dumps(params, sort_keys=True), mix["batch"],
                 mix["seq_len"], vocab)
    key = seed_key(seed, 1)
    return lambda step: draw(key, step)


@functools.lru_cache(maxsize=None)
def _draw(dist, params: str, batch: int, seq_len: int, vocab: int):
    import jax

    kw = json.loads(params)

    @jax.jit
    def draw(key, step):
        return dist.device(jax.random.fold_in(key, step), (batch, seq_len),
                           vocab, kw)

    return draw


class Prefill:
    """The timed path of one prefill cell for one seed."""

    def __init__(self, cell, seed: int, step_wrapper=None):
        import jax

        from bench import weights
        from bench.spec import model_config
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_step
        from repro.models.config import ShapeConfig

        self.cell = cell
        mix, model = cell.traffic, cell.model
        self.B, self.S = mix["batch"], mix["seq_len"]
        self.vocab = model["vocab"]
        cfg = model_config(model)
        bundle = build_step(cfg, ShapeConfig(name=cell.name, seq_len=self.S,
                                             global_batch=self.B,
                                             kind="prefill"),
                            make_mesh((1, 1), ("data", "model")),
                            multi_pod=False)
        expect = jax.tree.map(lambda s: tuple(s.shape), bundle.arg_structs[0])
        if expect != weights.shapes(model):
            raise ValueError("the benchmark's weight layout no longer matches "
                             "the program's parameter tree")
        self.step = jax.jit(bundle.step, in_shardings=bundle.in_shardings,
                            out_shardings=bundle.out_shardings)
        if step_wrapper is not None:
            self.step = step_wrapper(self.step)
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights and traffic of ``seed`` for the same compiled step."""
        from bench import weights

        self.seed = seed
        self.tokens = tokens_fn(self.cell.traffic, self.vocab, seed)
        self.params = weights.generate(self.cell.model, seed)
        self.outs = []

    def _call(self, i: int):
        return self.step(self.params, {"tokens": self.tokens(i)})

    def warm(self, n: int = 2) -> None:
        """``n`` steps; the last one's time sets how many to keep in
        flight."""
        for i in range(n):
            t = time.perf_counter()
            self._call(WARM_STEP + i).block_until_ready()
        self.depth = max(1, math.ceil(LEAD_S / (time.perf_counter() - t)))

    def hlo_text(self) -> str:
        return self.step.lower(
            self.params, {"tokens": self.tokens(0)}).compile().as_text()

    def window(self, seconds: float, annotate: bool = False):
        """Steps back to back for ``seconds``; returns (steps, seconds)."""
        from jax.profiler import TraceAnnotation

        span = TraceAnnotation if annotate else (
            lambda name: contextlib.nullcontext())
        self.outs = []
        t0 = time.perf_counter()
        last, self.slowest_s = None, 0.0
        while time.perf_counter() - t0 < seconds:
            with span("bench.dispatch"):
                self.outs.append(self._call(len(self.outs)))
            if len(self.outs) > self.depth:
                with span("bench.wait"):
                    self.outs[-1 - self.depth].block_until_ready()
                now = time.perf_counter()
                if last is not None:     # steps apart once the queue is full
                    self.slowest_s = max(self.slowest_s, now - last)
                last = now
        with span("bench.wait"):
            self.outs[-1].block_until_ready()
        return len(self.outs), time.perf_counter() - t0

    def picks(self, n: int) -> list:
        """Up to ``n`` processed sequences drawn from the seed, as
        (step, row) pairs in order."""
        rng = np.random.default_rng([self.seed, 2])
        total = len(self.outs) * self.B
        picks = rng.choice(total, size=min(n, total), replace=False)
        return [divmod(int(p), self.B) for p in sorted(picks)]

    def sample(self, n: int):
        """Up to ``n`` processed sequences drawn from the seed: their
        tokens [n, S] and served logits [n, vocab] (float32)."""
        toks, logits = [], []
        for s, r in self.picks(n):
            toks.append(np.asarray(self.tokens(s))[r])
            logits.append(np.asarray(self.outs[s][r, :self.vocab],
                                     np.float32))
        return np.stack(toks), np.stack(logits)

    def router_logits(self, probe, n: int) -> list:
        """The program's router logits of the sampled sequences, through
        the compiled step again with ``probe`` armed: one array
        [n, S, n_experts] a MoE layer."""
        picks = self.picks(n)
        by_step = {s: probe.capture(lambda: self._call(s))
                   for s in sorted({s for s, _ in picks})}
        return [np.stack([by_step[s][layer][r] for s, r in picks])
                for layer in range(len(by_step[picks[0][0]]))]

    def free(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.params) + self.outs:
            leaf.delete()
        self.params, self.outs = None, []


def check(cell, seed: int, tokens, served, reference=None, control=None,
          router: list = None):
    """Compare served logits with the plain reference (regenerated
    weights).  ``control``, a reference in a lower precision, takes the
    program's place when given; ``router``, when given, receives the
    reference's router logits."""
    import jax

    from bench import compare, weights
    from bench.reference import Reference

    model = cell.model
    ref = reference or Reference(model, cell.config.get("routing"))
    params = weights.generate(model, seed)
    want, margins = ref.last_logits(params, tokens, router)
    if control is not None:
        served, _ = control.last_logits(params, tokens)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    return compare.judge(served, want, margins, cell.check["tie_margin"],
                         cell.check["limits"]["logit_err"])


def calibrate(cell, seeds, control_seeds, seconds: float, probe=None):
    """Per seed, in one process: a window of ``seconds`` through the same
    compiled step, then the reference on its sample (and, on the control
    seeds, the control in the program's place).  With a router ``probe``
    installed, also how far the program's router logits of the sample lie
    from the reference's."""
    from bench import compare
    from bench.reference import Reference

    routing = cell.config.get("routing")
    ref = Reference(cell.model, routing)
    control = Reference(cell.model, routing, "fp8")
    n = cell.check["check_sequences"]
    p = None
    for seed in seeds:
        if p is None:
            p = Prefill(cell, seed)
        else:
            p.reseed(seed)
        p.warm(1)
        p.window(seconds)
        toks, served = p.sample(n)
        program = p.router_logits(probe, n) if probe else None
        p.free()
        reference = [] if probe else None
        line = {"seed": seed, "program": check(cell, seed, toks, served,
                                               reference=ref,
                                               router=reference)}
        if probe:
            line["router"] = compare.router_shift(
                program, reference, cell.model["moe"]["top_k"])
        if seed in control_seeds:
            line["control"] = check(cell, seed, toks, served, reference=ref,
                                    control=control)
        yield line


def run(cell, seed: int, seconds: float, *, trace: bool, clock,
        step_wrapper=None, trace_dir=None) -> dict:
    """One run: set-up, the window (traced or not), then the check."""
    from bench import counts

    t_import = clock.setup_done()
    p = Prefill(cell, seed, step_wrapper)
    t_build = clock.setup_done()
    p.warm()
    out = {"setup_s": clock.end_setup()}
    out["notes"] = {"setup_phases_s": {
        "imports": round(t_import, 3), "build_and_weights": round(
            t_build - t_import, 3),
        "warm": round(out["setup_s"] - t_build, 3)}}
    hlo = [p.hlo_text()] if trace else None
    with clock.window():
        if trace:
            import jax
            with jax.profiler.trace(trace_dir):
                steps, secs = p.window(seconds, annotate=True)
        else:
            steps, secs = p.window(seconds)
    seqs = steps * p.B
    out.update(steps=steps, window_s=secs, attempted=seqs, hlo=hlo,
               e2e={"prefill_tok_s": seqs * p.S / secs},
               flops_per_step=counts.prefill_flops(cell.model, p.B, p.S))
    if cell.model.get("moe"):
        out["fabric_scatter"] = True     # the dispatch's is the only scatter
        out["fabric_bytes_per_step"] = counts.fabric_bytes(
            cell.model, p.B * p.S, cell.config["routing"]["group_tokens"])
    out["memory_peak_bytes"] = clock.memory_peak()
    tokens, served = p.sample(cell.check["check_sequences"])
    p.free()
    t_ref = time.perf_counter()
    verdict = check(cell, seed, tokens, served)
    limit = cell.check["limits"]["logit_err"]
    out["checks"] = {"logit_err": (verdict["logit_err"], limit)}
    out["notes"].update(in_flight=p.depth,
                        slowest_step_s=round(p.slowest_s, 4),
                        compared=verdict["compared"],
                        router_ties=verdict["ties"],
                        reference_s=round(time.perf_counter() - t_ref, 3))
    out["failed"] = verdict["failed"]
    out["correct"] = bool(verdict["logit_err"] <= limit)
    return out
