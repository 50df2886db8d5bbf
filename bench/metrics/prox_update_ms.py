"""Prox-LEAD elementwise update: device ms per step of the ops of
``update_ms`` under the program's scope ``prox_lead/update``: z and the
diffs, zhat, D, H, Hw, X and the prox."""
from bench import scopes


def read(ctx):
    return scopes.update_part_ms(ctx, scopes.UPDATE)
