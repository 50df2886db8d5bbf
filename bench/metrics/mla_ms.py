"""Latent attention: device ms per step of the forward+backward ops under
the program's scope ``mla`` (projections, rope, attention and its output
projection of every MLA block, forward and transpose)."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "mla", "fwd_bwd")
