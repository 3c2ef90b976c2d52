"""prefill_mfu: the FLOPs the window's prefill steps require (bench's own
count, the architecture's ``prefill_flops``) over the window's host-clock
seconds and the chips' published peak, in percent."""


def read(ctx):
    if not ctx.get("steps") or not ctx.get("flops_per_step"):
        return None
    peak = ctx["chips"] * ctx["peaks"].flops
    return 100.0 * ctx["steps"] * ctx["flops_per_step"] / ctx["window_s"] / peak
