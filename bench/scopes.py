"""Which part of the train step a device op belongs to, by the named scope
the program put it under (``jax.named_scope``; the names are the program's
``repro.obs`` constants, copied here so that the benchmark imports nothing
of the program).  A scope shows in the op's HLO ``op_name`` as a run of
whole path segments.  An op of a program that names no scope, such as a
build from before the scopes, lies under none, and a reader of it finds
nothing to read.

An op belongs to one scope of those in question, the six below and the
one a reader asks for by name (``part_ms``): of the names XLA merged into
its ``op_name`` (joined with ';'), the first that holds one of them
decides, and of those that name holds, the innermost.  The six never nest
in one another; a scope asked for by name may nest in one of them, such as
a part of the model under ``fwd_bwd``.

The readers of the update's parts count only ops that ``bench/layers.py``
puts in ``other``, the ops ``update_ms`` sums: so they never count a
kernel, a permute or forward+backward, and what they leave of
``update_ms`` is the unscoped remainder and the exchange."""
from __future__ import annotations

from typing import List, Optional, Sequence

from bench import layers

FWD_BWD = "fwd_bwd"
NOISE = "prox_lead/noise"
WIRE_LAYOUT = "prox_lead/wire_layout"
EXCHANGE = "prox_lead/exchange"
UPDATE = "prox_lead/update"
CONSENSUS = "consensus"
SCOPES = (FWD_BWD, NOISE, WIRE_LAYOUT, EXCHANGE, UPDATE, CONSENSUS)


def _end(name: str, scope: str) -> int:
    """Where the innermost run of ``scope``'s segments ends in one name,
    or -1 where the name does not hold it."""
    at = ("/" + name + "/").rfind("/" + scope + "/")
    return -1 if at < 0 else at + len(scope)


def scopes_in(op_name: str) -> List[str]:
    """Every scope of SCOPES that ``op_name`` names, in SCOPES order."""
    names = op_name.split(";")
    return [s for s in SCOPES if any(_end(n, s) >= 0 for n in names)]


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The scope of ``scopes`` an op belongs to by the rule in the module
    doc, or None where it lies under none of them."""
    for name in op_name.split(";"):
        held = [(_end(name, s), len(s), s) for s in scopes
                if _end(name, s) >= 0]
        if held:
            return max(held)[2]
    return None


def part_ms(ctx, scope: str, layer: str) -> Optional[float]:
    """Device ms per step (per chip) of the ops of ``layer`` (as
    ``layers.classify`` names it) that belong to ``scope``, any scope name;
    None where the trace holds none."""
    meta = ctx.meta()
    among = SCOPES + (scope,)
    picked = [o.dur_ns for o in ctx.table.ops
              if layers.classify(o.name, meta) == layer
              and scope_of(meta.get(o.name, ("", ""))[1], among) == scope]
    if not picked or not ctx.steps:
        return None
    return sum(picked) / len(ctx.table.devices) / 1e6 / ctx.steps
