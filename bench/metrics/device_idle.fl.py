"""Share of the traced window in which no operation ran on the device:
1 - union of op intervals / window, averaged over chips, in %."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0 or not t["chips"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
