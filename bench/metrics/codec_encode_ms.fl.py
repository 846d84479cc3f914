"""Device time per round of the ops under the ``codec-encode`` scope, on
the busiest chip, in ms."""


def read(ctx):
    t = ctx["trace"]
    secs = max((s.get("codec-encode", 0.0) for s in t["scope_s"]), default=0.0)
    if secs <= 0 or not ctx["rounds"]:
        return None
    return 1e3 * secs / ctx["rounds"]
