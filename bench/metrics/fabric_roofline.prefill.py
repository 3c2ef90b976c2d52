"""fabric_roofline.prefill: the least time the chip needs to move the
bytes any crossbar must move for the window's steps (the architecture's
``fabric_bytes`` at the peak HBM bandwidth) over the device time of the
fabric's ops (as ``fabric_share`` finds them), in percent."""
from bench.trace import FABRIC_PATHS, fabric_s

PATHS = FABRIC_PATHS


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("fabric_bytes_per_step") or fabric_s(ctx) <= 0:
        return None
    least = ctx["steps"] * ctx["fabric_bytes_per_step"] / ctx["peaks"].hbm_bw
    return 100.0 * least / fabric_s(ctx)
