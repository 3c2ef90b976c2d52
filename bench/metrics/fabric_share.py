"""fabric_share.<cell kind>: device time of the crossbar fabric's ops
over the device's busy time, in percent.

The fabric's ops are those whose op-name path, or program, passes through
the fabric's jitted pieces (``bench.trace.FABRIC``): the MoE layer's
routing inside the model's programs and, while serving, the server's own
routing programs; in a prefill step with experts also the packet scatter
that the compiler leaves without an op name (``bench.trace.fabric_s``)."""
from bench.trace import FABRIC_PATHS, fabric_s

PATHS = FABRIC_PATHS


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or fabric_s(ctx) <= 0:
        return None
    return 100.0 * fabric_s(ctx) / t["busy_s"]
