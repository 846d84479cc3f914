"""The held experts' grouped products against their roofline: the FLOPs of
the token-slots they computed in the traced rounds (three products of
2 d f per slot, forward and the backward's two, from shapes) over the bf16
peak, against the device time of the ops under the program's
``expert-compute`` scope, per chip, in %. At the cell's ~384 slots per
expert the products are compute-bound (about 380 FLOP per weight byte,
above the v5e ridge of 240), so the FLOP bound is the roofline."""
from bench.lib.scope_time import seconds


def read(ctx):
    c = ctx["counts"]
    secs = seconds(ctx, "expert-compute") / max(ctx["chips"], 1)
    if secs <= 0 or not c.get("moe_slots_held"):
        return None
    least = (c["expert_gmm_flops_per_slot"] * c["moe_slots_held"]
             / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / secs
