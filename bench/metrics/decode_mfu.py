"""decode_mfu: the FLOPs the window's served tokens require (bench's own
count, the architecture's ``token_flops`` at each token's position) over
the window's host-clock seconds and the chips' published peak, in
percent."""


def read(ctx):
    if not ctx.get("token_flops") or not ctx.get("window_s"):
        return None
    peak = ctx["chips"] * ctx["peaks"].flops
    return 100.0 * ctx["token_flops"] / ctx["window_s"] / peak
