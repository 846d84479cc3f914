"""The int8 error-feedback encode's share of its HBM roofline: the least
bytes for S uploads of P floats (read upload and residual; write int8
payload, scales, residual) over the peak bandwidth, against the device time
per round of the ops under the ``codec-encode`` scope, in %."""


def read(ctx):
    t = ctx["trace"]
    need = ctx["counts"].get("encode_bytes_per_round")
    secs = t["scope_s"][0].get("codec-encode", 0.0) if t["scope_s"] else 0.0
    if need is None or secs <= 0 or not ctx["rounds"]:
        return None
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / ctx["rounds"])
