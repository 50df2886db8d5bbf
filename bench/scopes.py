"""Which part of the train step a device op belongs to, by the named scope
the program put it under (``jax.named_scope``; the names are the program's
``repro.obs`` constants, copied here so that the benchmark imports nothing
of the program).  A scope shows in the op's HLO ``op_name`` as a run of
whole path segments; the program never nests one scope in another.  An op
of a program that names no scope, such as a build from before the scopes,
lies under none, and a reader of it finds nothing to read.

The readers of the update's parts count only ops that ``bench/layers.py``
puts in ``other``, the ops ``update_ms`` sums: so they never count a
kernel, a permute or forward+backward, and what they leave of
``update_ms`` is the unscoped remainder and the exchange."""
from __future__ import annotations

from typing import List, Optional

from bench import layers

FWD_BWD = "fwd_bwd"
NOISE = "prox_lead/noise"
WIRE_LAYOUT = "prox_lead/wire_layout"
EXCHANGE = "prox_lead/exchange"
UPDATE = "prox_lead/update"
CONSENSUS = "consensus"
SCOPES = (FWD_BWD, NOISE, WIRE_LAYOUT, EXCHANGE, UPDATE, CONSENSUS)


def scopes_in(op_name: str) -> List[str]:
    """Every scope that ``op_name`` names, in SCOPES order.  XLA joins the
    names of ops it merged with ';'."""
    paths = ["/" + name + "/" for name in op_name.split(";")]
    return [s for s in SCOPES if any("/" + s + "/" in p for p in paths)]


def scope_of(op_name: str) -> Optional[str]:
    """The scope of an op, or None where it lies under none.  Of merged
    names (joined with ';'), the first that holds a scope decides."""
    for name in op_name.split(";"):
        found = scopes_in(name)
        if found:
            return found[0]
    return None


def update_part_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per step (per chip) of the ``other`` ops under ``scope``;
    None where the trace holds none."""
    meta = ctx.meta()
    picked = [o.dur_ns for o in ctx.table.ops
              if layers.classify(o.name, meta) == "other"
              and scope_of(meta.get(o.name, ("", ""))[1]) == scope]
    if not picked or not ctx.steps:
        return None
    return sum(picked) / len(ctx.table.devices) / 1e6 / ctx.steps
