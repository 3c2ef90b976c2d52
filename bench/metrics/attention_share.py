"""attention_share.<cell kind>: device time of the ops under the
program's ``attn.core`` op-name scope (``models/attention``: the chunked
prefill attention and the cached decode attention, without the
projections) over the device's busy time, in percent.  Silent where no op
carries the scope."""

PATHS = {"attention": r"attn\.core"}


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or not t["path_s"].get("attention"):
        return None
    return 100.0 * t["path_s"]["attention"] / t["busy_s"]
