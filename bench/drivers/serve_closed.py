"""Closed-loop serving: ``clients`` callers against one tenant in a
``Shell`` behind an ``ElasticServer``; the timed entry is
``ElasticServer.step()``.

The mix (``"kind": "serve_closed"``) has ``clients`` callers, each with
one request in flight: a caller sends its next request on the tick after
its last one completes.  Every caller cycles through the same ``cycle``
request sizes: prompt lengths taken in turn from ``prompt_lens``, and
``max_new`` at evenly spaced quantiles of a Pareto law clipped to [min,
max].  A seed only chooses which caller follows which stream of sizes and
draws the token ids, so every seed offers the same work in another order.

Set-up builds the shell and the server, registers the model (the engine
draws weights of its own, which are replaced at once by the benchmark's
weights from the seed) and warms every admission shape (each batch size up
to the slot count at each prompt length), the decode step and the server's
fabric.  The window then starts every caller and steps the server until
``seconds`` have passed.  A token is delivered when the tick that appended
it returns; the gaps between a request's consecutive tokens are its
inter-token latencies.  After the window a sample of the finished requests
(drawn from the seed, the longest among them) is checked against the plain
reference, teacher-forced on the served tokens.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

APP = 0
GB = 1 << 30


def validate(mix: dict) -> None:
    for key in ("clients", "cycle"):
        if not isinstance(mix.get(key), int) or mix[key] < 1:
            raise ValueError(f"serve mix needs a positive {key}: {mix}")
    lens = mix.get("prompt_lens")
    if not lens or any(not isinstance(n, int) or n < 1 for n in lens):
        raise ValueError(f"serve mix needs prompt_lens: {mix}")
    mn = mix.get("max_new", {})
    if (mn.get("dist") != "pareto" or mn.get("alpha", 0) <= 0
            or not 1 <= mn.get("min", 0) <= mn.get("max", 0)):
        raise ValueError(f"serve mix needs a clipped pareto max_new: {mix}")
    srv = mix.get("server", {})
    if not all(isinstance(srv.get(k), int) and srv[k] >= 1
               for k in ("n_slots", "max_len", "regions")):
        raise ValueError(f"serve mix needs a server block: {mix}")


def cycle_sizes(mix: dict) -> list:
    """The ``cycle`` (prompt length, max_new) pairs every caller goes
    through, in a fixed order."""
    mn, n = mix["max_new"], mix["cycle"]
    lens = mix["prompt_lens"]
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        pareto = mn["min"] * (1.0 - q) ** (-1.0 / mn["alpha"])
        out.append((lens[i % len(lens)], int(min(mn["max"], pareto))))
    return out


def closed_requests(mix: dict, vocab: int, seed: int):
    """``(client, j) -> (prompt [S] int32, max_new)``: the j-th request of
    a caller, for this seed.  The cycle's sizes are dealt, in snake order
    of their quantiles, into blocks of one request a caller, so that each
    block spans the whole law; the j-th requests of all callers share
    block j mod blocks out in a fixed order.  A seed only rotates which
    caller follows which stream of sizes and draws the token ids, so every
    seed offers the same work, and a window of any length serves the same
    requests, in another order."""
    from bench.traffic import rng, tokens

    dist, params = tokens(mix)
    sizes, k = cycle_sizes(mix), mix["clients"]
    n_blocks = -(-len(sizes) // k)
    blocks = [[] for _ in range(n_blocks)]
    for i, size in enumerate(sizes):
        b = i % n_blocks
        blocks[b if (i // n_blocks) % 2 == 0 else n_blocks - 1 - b].append(
            size)
    rotation = int(rng(seed, 3).integers(k))

    def request(client: int, j: int):
        block = blocks[j % n_blocks]
        stream = (client + rotation) % k
        S, max_new = block[rng(0, 3, j).permutation(k)[stream] % len(block)]
        prompt = dist.host(rng(seed, 4, client, j), S, vocab, params)
        return prompt, max_new

    return request


class Served:
    """The timed path of one serving cell for one seed."""

    def __init__(self, cell, seed: int, step_wrapper=None):
        import jax

        from bench import weights
        from bench.spec import model_config
        from repro.core.elastic import Region
        from repro.core.module import ModuleFootprint
        from repro.shell import Shell, Submit
        from repro.shell.server import ElasticServer

        self.cell, self.seed = cell, seed
        mix, model = cell.traffic, cell.model
        srv = mix["server"]
        self.vocab, self.max_len = model["vocab"], srv["max_len"]
        layer_bytes = (2 * weights.n_params(cell.arch.layout(model))
                       // model["n_layers"])
        fp = ModuleFootprint(param_bytes=layer_bytes, flops_per_token=0.0,
                             activation_bytes_per_token=2 * model["d_model"])
        shell = Shell([Region(rid=i, n_chips=1, hbm_bytes=16 * GB)
                       for i in range(srv["regions"])])
        shell.post(Submit(tenant=cell.config["name"],
                          footprints=(fp,) * model["n_layers"], app_id=APP))
        self.server = ElasticServer(shell, n_slots=srv["n_slots"],
                                    fabric_backend=srv["fabric_backend"])
        self.server.register_model(APP, model_config(model),
                                   max_len=self.max_len)
        self.engine = self.server.engine(APP)
        got = jax.tree.map(lambda x: tuple(x.shape), self.engine.params)
        if got != weights.shapes(cell.arch.layout(model)):
            raise ValueError("the benchmark's weight layout no longer matches "
                             "the engine's parameter tree")
        for leaf in jax.tree.leaves(self.engine.params):
            leaf.delete()
        self.reseed(seed)
        self.stall_s = 0.0
        self._span = lambda name: contextlib.nullcontext()
        prefill, decode = self.engine.prefill_batch, self.engine.decode
        if step_wrapper is not None:
            prefill, decode = step_wrapper(prefill, decode)

        def timed_prefill(prompts):
            t = time.perf_counter()
            with self._span("bench.prefill"):
                out = prefill(prompts)
            self.stall_s += time.perf_counter() - t
            return out

        def spanned_decode(tok, state):
            with self._span("bench.decode"):
                return decode(tok, state)

        self.engine.prefill_batch = timed_prefill
        self.engine.decode = spanned_decode

    def reseed(self, seed: int) -> None:
        """Weights and requests of ``seed`` for the same server and
        compiled programs."""
        self.seed = seed
        self.engine.params = self.cell.weights(seed)
        self.requests = closed_requests(self.cell.traffic, self.vocab, seed)

    def warm(self) -> None:
        """Every admission shape, the decode step and the fabric, through
        the server itself; then an empty server at tick zero."""
        srv = self.cell.traffic["server"]
        rng = np.random.default_rng(0)
        for S in self.cell.traffic["prompt_lens"]:
            for B in range(1, srv["n_slots"] + 1):
                prompts = rng.integers(0, self.vocab, (B, S), np.int32)
                for tok, state in self.engine.prefill_batch(list(prompts)):
                    self.engine.decode(tok, state)
        self._serve_closed(max_ticks=3)
        self.server.reset()

    def _serve_closed(self, seconds: float = None, max_ticks: int = None):
        """The closed loop until ``seconds`` or ``max_ticks``: ticks,
        seconds, tokens delivered and the FLOPs they require, the finished
        requests {rid: (client, prompt, tokens)} and the token gaps."""
        from repro.shell.server import StreamRequest

        server, clients = self.server, self.cell.traffic["clients"]
        owner, sent, prompts = {}, [0] * clients, {}

        def send(c):
            prompt, max_new = self.requests(c, sent[c])
            sent[c] += 1
            rid = server.submit(StreamRequest(app_id=APP, prompt=prompt,
                                              max_new=max_new))
            owner[rid], prompts[rid] = c, prompt

        for c in range(clients):
            send(c)
        last, gaps, served, ticks = {}, [], {}, 0
        delivered = flops = 0
        t0 = prev = time.perf_counter()
        cpu0, slowest = time.thread_time(), 0.0
        while True:
            with self._span("bench.tick"):
                finished = server.step()
            now = time.perf_counter()
            ticks += 1
            slowest, prev = max(slowest, now - prev), now
            got = [(s.request.rid, len(s.produced)) for s in server.slots
                   if s is not None]
            got += [(c.rid, len(c.tokens)) for c in finished]
            delivered += len(got)
            for rid, n in got:
                flops += self.cell.arch.token_flops(
                    self.cell.model, len(prompts[rid]) + n - 1)
                if rid in last:
                    gaps.append(now - last[rid])
                last[rid] = now
            for comp in finished:
                del last[comp.rid]
                served[comp.rid] = (owner[comp.rid], prompts.pop(comp.rid),
                                    comp.tokens)
                send(owner[comp.rid])
            if ((seconds is not None and now - t0 >= seconds)
                    or (max_ticks is not None and ticks >= max_ticks)):
                break
        return {"ticks": ticks, "seconds": now - t0, "tokens": delivered,
                "flops": flops, "served": served, "gaps": gaps,
                "slowest_tick_s": slowest,
                "host_cpu_s": time.thread_time() - cpu0}

    def window(self, seconds: float, annotate: bool = False):
        from jax.profiler import TraceAnnotation

        if annotate:
            self._span = TraceAnnotation
        self.stall_s = 0.0
        try:
            return self._serve_closed(seconds=seconds)
        finally:
            self._span = lambda name: contextlib.nullcontext()

    def hlo_texts(self) -> list:
        """Compiled HLO of the engine's decode step and admission programs
        (loaded from the compile cache), so that the trace's ops find
        their op-name paths; empty where the engine keeps them otherwise."""
        import jax.numpy as jnp

        e = self.engine
        try:
            state = e.model.init_decode_state(1, self.max_len)
            batch = {"tokens": jnp.zeros((1, 1), jnp.int32), **e._extras}
            texts = [e._decode_fn.lower(e.params, state, batch)
                     .compile().as_text()]
            for (B, S), fn in e._prefill_fns.items():
                texts.append(fn.lower(e.params, jnp.zeros((B, S), jnp.int32))
                             .compile().as_text())
        except AttributeError:
            return []
        return texts

    def free(self) -> None:
        import jax

        leaves = jax.tree.leaves(self.engine.params)
        for s in self.server.slots:
            if s is not None:
                leaves += jax.tree.leaves(s.state)
        self.server.reset()
        for leaf in leaves:
            leaf.delete()
        self.engine.params = None


def sample(served: dict, n: int, seed: int) -> list:
    """Up to ``n`` finished requests drawn from the seed, the longest
    (prompt and served tokens) always among them: [(prompt, tokens)]."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r][1]) + len(served[r][2]),
                                       -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([seed % 2**64, 2])
    picks = [longest] + [rest[i] for i in
                         rng.choice(len(rest), min(n - 1, len(rest)),
                                    replace=False)]
    return [(served[r][1], served[r][2]) for r in sorted(picks)]


def teacher_forced(picked: list, max_len: int, n_rows: int):
    """Token rows [n_rows, max_len] (prompt, then the served tokens but
    the last, zero-padded at the end; rows past the sample are padding, so
    every run shares one reference program) and, per request, the
    positions whose next token was served and those tokens."""
    rows = np.zeros((max(n_rows, len(picked)), max_len), np.int32)
    pos, want = [], []
    for i, (prompt, toks) in enumerate(picked):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        rows[i, :len(seq)] = seq
        pos.append(np.arange(len(prompt) - 1, len(seq)))
        want.append(np.asarray(toks, np.int32))
    return rows, pos, want


def check(cell, seed: int, picked: list, reference=None, control=None,
          router: list = None):
    """The widest gap by which a served token's reference logit lies below
    the reference's best at its position, over positions whose router is
    not near-tied in the reference.  ``control``, a reference in a lower
    precision, takes the program's place: its own first choices are read
    in the reference instead of the served tokens.  ``router``, when
    given, receives the reference's router logits of the rows."""
    import jax
    import jax.numpy as jnp

    from bench import compare

    ref = reference or cell.arch.Reference(cell.model, None)
    rows, pos, want = teacher_forced(picked, cell.traffic["server"]["max_len"],
                                     cell.check["check_requests"])
    params = cell.weights(seed)
    logits, margins = ref.all_logits(params, rows, router)
    if control is not None:
        firsts, _ = control.all_logits(params, rows)
        firsts = np.asarray(jnp.argmax(firsts, -1))
        want = [firsts[i, p] for i, p in enumerate(pos)]
    best = np.asarray(jnp.max(logits, -1))
    chosen = [np.asarray(logits[i, p, w]) for i, (p, w) in
              enumerate(zip(pos, want))]
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del logits
    gaps = [best[i, p] - c for i, (p, c) in enumerate(zip(pos, chosen))]
    ties = [margins[i, p] for i, p in enumerate(pos)]
    return compare.judge_gaps(gaps, ties, cell.check["tie_margin"],
                              cell.check["limits"]["token_gap"])


def router_logits(served: "Served", probe, picked: list) -> list:
    """The program's router logits at every position of the sampled
    requests (prompt, then the served tokens), replayed token by token
    through the engine's own admission program with ``probe`` armed: one
    list of [len, n_experts] arrays a MoE layer."""
    max_len = served.cell.traffic["server"]["max_len"]
    rows, _, _ = teacher_forced(picked, max_len, len(picked))
    L = max_len - 1
    fn = served.engine._prefill_fn(len(rows), L)
    got = probe.capture(lambda: fn(served.engine.params, rows[:, :L]))
    n_layers = len(got) // L
    steps = np.stack([g.reshape(len(rows), -1) for g in got]).reshape(
        L, n_layers, len(rows), -1)
    lens = [len(p) + len(t) - 1 for p, t in picked]
    return [[steps[:n, layer, i] for i, n in enumerate(lens)]
            for layer in range(n_layers)]


def calibrate(cell, seeds, control_seeds, seconds: float, probe=None):
    """Per seed, in one process: a window of ``seconds`` on the same
    server and programs, then the reference on its sample (and, on the
    control seeds, the control in the program's place).  With a router
    ``probe`` installed, also how far the program's router logits of the
    sample lie from the reference's."""
    from bench import compare

    ref = cell.arch.Reference(cell.model, None)
    control = cell.arch.Reference(cell.model, None, "fp8")
    s = None
    for seed in seeds:
        if s is None:
            s = Served(cell, seed)
            s.warm()
        else:
            s.reseed(seed)
        w = s.window(seconds)
        picked = sample(w["served"], cell.check["check_requests"], seed)
        program = router_logits(s, probe, picked) if probe else None
        s.free()
        reference = [] if probe else None
        line = {"seed": seed, "requests": len(w["served"]),
                "tokens": sum(len(t) for _, t in picked),
                "program": check(cell, seed, picked, reference=ref,
                                 router=reference)}
        if probe:
            lens = [len(p) + len(t) - 1 for p, t in picked]
            reference = [[r[i, :n] for i, n in enumerate(lens)]
                         for r in reference]
            line["router"] = compare.router_shift(
                [np.concatenate(a) for a in program],
                [np.concatenate(a) for a in reference],
                cell.model["moe"]["top_k"])
        if seed in control_seeds:
            line["control"] = check(cell, seed, picked, reference=ref,
                                    control=control)
        yield line


def run(cell, seed: int, seconds: float, *, trace: bool, clock,
        step_wrapper=None, trace_dir=None) -> dict:
    """One run: set-up, the window (traced or not), then the check."""
    t_import = clock.setup_done()
    s = Served(cell, seed, step_wrapper)
    t_build = clock.setup_done()
    s.warm()
    out = {"setup_s": clock.end_setup()}
    out["notes"] = {"setup_phases_s": {
        "imports": round(t_import, 3),
        "build_and_weights": round(t_build - t_import, 3),
        "warm": round(out["setup_s"] - t_build, 3)}}
    hlo = s.hlo_texts() if trace else None
    with clock.window():
        if trace:
            import jax
            with jax.profiler.trace(trace_dir):
                w = s.window(seconds, annotate=True)
        else:
            w = s.window(seconds)
    served, gaps = w["served"], w["gaps"]
    out.update(steps=w["ticks"], window_s=w["seconds"],
               attempted=len(served), hlo=hlo, stall_s=s.stall_s,
               tokens=w["tokens"], token_flops=w["flops"],
               e2e={"decode_tok_s": w["tokens"] / w["seconds"],
                    "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))
                    if gaps else float("nan")})
    out["memory_peak_bytes"] = clock.memory_peak()
    picked = sample(served, cell.check["check_requests"], seed)
    s.free()
    t_ref = time.perf_counter()
    verdict = check(cell, seed, picked)
    limit = cell.check["limits"]["token_gap"]
    out["checks"] = {"token_gap": (verdict["token_gap"], limit)}
    out["notes"].update(slowest_tick_s=round(w["slowest_tick_s"], 4),
                        host_cpu_s=round(w["host_cpu_s"], 3),
                        requests=len(served), gaps=len(gaps),
                        compared=verdict["compared"],
                        router_ties=verdict["ties"],
                        reference_s=round(time.perf_counter() - t_ref, 3))
    out["failed"] = verdict["failed"]
    out["correct"] = bool(verdict["token_gap"] <= limit and picked)
    return out
