"""Client forward + backward FLOPs of the traced rounds (6 P per row, from
shapes) over window time x chips x the bf16 peak, in %."""


def read(ctx):
    t = ctx["trace"]
    if not ctx["rounds"] or t["window_s"] <= 0:
        return None
    flops = ctx["counts"]["flops_per_round"] * ctx["rounds"]
    return 100.0 * flops / (t["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
