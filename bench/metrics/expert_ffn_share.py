"""expert_ffn_share.<cell kind>: device time of the ops under the
program's ``moe.expert_ffn`` op-name scope (``models/moe._expert_ffn``,
the expert MLP of every dispatch path, at prefill and decode) over the
device's busy time, in percent.  Silent where no op carries the scope."""

PATHS = {"expert_ffn": r"moe\.expert_ffn"}


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or not t["path_s"].get("expert_ffn"):
        return None
    return 100.0 * t["path_s"]["expert_ffn"] / t["busy_s"]
