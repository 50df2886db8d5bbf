"""Consensus metric: device ms per step of the ops of ``update_ms``
under the program's scope ``consensus``: each node's squared distance
from the node mean, summed over the leaves, which the step computes every
time."""
from bench import scopes


def read(ctx):
    return scopes.update_part_ms(ctx, scopes.CONSENSUS)
