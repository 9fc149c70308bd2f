"""mfu: model FLOPs of the requests completed ok in the traced window
(``nfe`` field evaluations, the tail layers and the readout each, as the
configuration's layout counts them: ``counts.request_flops``) over
window x chips x peak bf16 FLOP/s, in %."""
import counts


def read(ctx):
    ok = [c for c in ctx.completions if c["status"] == "ok"]
    if not ok or ctx.window_s <= 0:
        return None
    flops = sum(counts.request_flops(ctx.layout, ctx.sizes, ctx.prompt_len,
                                     c["nfe"]) for c in ok)
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
