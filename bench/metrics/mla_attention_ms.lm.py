"""Device time per round of the ops under the program's ``mla-attention``
scope (latent attention: projections, RoPE, the chunked causal attention,
forward and backward), per chip, in ms."""
from bench.lib.scope_time import ms_per_round


def read(ctx):
    return ms_per_round(ctx, "mla-attention")
