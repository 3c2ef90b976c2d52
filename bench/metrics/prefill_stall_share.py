"""prefill_stall_share: the share of the window the server spent in
admission prefill (host time inside the engine's ``prefill_batch``, timed
by the benchmark's wrapper; every slot waits meanwhile), in percent."""


def read(ctx):
    if not ctx.get("window_s") or "stall_s" not in ctx:
        return None
    return 100.0 * ctx["stall_s"] / ctx["window_s"]
