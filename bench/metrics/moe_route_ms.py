"""Expert routing: device ms per step of the forward+backward ops under the
program's scope ``moe/route``: router, top-k, balance loss, the grouping of
(token, slot) pairs by held expert and the weighted combine."""
from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "moe/route", "fwd_bwd")
