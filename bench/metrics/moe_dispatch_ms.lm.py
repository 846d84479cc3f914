"""Device time per round of the ops under the program's ``moe-dispatch``
scope (router, top-k, the sort and gather of token-slots by held expert,
the weighted scatter back, forward and backward), per chip, in ms."""
from bench.lib.scope_time import ms_per_round


def read(ctx):
    return ms_per_round(ctx, "moe-dispatch")
