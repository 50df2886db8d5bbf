"""Stochastic-rounding noise: device ms per step of the ops of
``update_ms`` under the program's scope ``prox_lead/noise``: the key folds
and the U[0,1) draws."""
from bench import scopes


def read(ctx):
    return scopes.update_part_ms(ctx, scopes.NOISE)
