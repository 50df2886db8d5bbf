"""Which device op belongs to which layer: the one place that names them.

A traced op is named as its HLO instruction (``fusion.12``,
``collective-permute-start.3``, ...); the compiled step's HLO text gives
each instruction its opcode and the ``op_name`` JAX wrote into its
metadata, the name stack of the code that made it.  The keys below read
that name stack, so a later change that renames a kernel's entry point or
adds ``jax.named_scope`` to the program re-points them here."""
from __future__ import annotations

import re
from typing import Dict, Tuple

# name-stack keys (substrings of op_name)
FWD_BWD = ("jvp(",)                        # forward, and transpose(jvp( ...
QUANT_PACK = ("jit(qinf_quantize_pack)",)  # kernels/ops.py entry point
MIX = ("jit(qinf_unpack_dequant_mix)",)    # kernels/ops.py entry point
# opcodes of the gossip exchange
PERMUTE_OPCODES = ("collective-permute", "collective-permute-start",
                   "collective-permute-done")

# ops whose trace event spans the ops of the computations they call, which
# the trace lists as events of their own
CONTAINER_OPCODES = ("while", "conditional", "call")

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*.*?\s"
    r"(?P<opcode>[a-z][\w\-]*)\((?P<rest>.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_meta(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (opcode, op_name)} of a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        on = _OP_NAME.search(line)
        out[m.group("name")] = (m.group("opcode"), on.group(1) if on else "")
    return out


def layer_of(opcode: str, op_name: str) -> str:
    if opcode in PERMUTE_OPCODES:
        return "permute"
    if any(k in op_name for k in QUANT_PACK):
        return "quant_pack"
    if any(k in op_name for k in MIX):
        return "mix"
    if any(k in op_name for k in FWD_BWD):
        return "fwd_bwd"
    return "other"


_EVENT = re.compile(r"%?([\w.\-]+)")


def instruction(event_name: str) -> str:
    """The HLO instruction a trace event names: on a TPU the event's name
    is the instruction's text, ``%fusion.12 = bf16[...] fusion(...)``."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def classify(event_name: str, meta: Dict[str, Tuple[str, str]]) -> str:
    """The layer of a traced op: fwd_bwd, quant_pack, mix, permute or
    other, or 'container' for an op whose event covers other events.  An
    op the HLO does not name is 'other'."""
    name = instruction(event_name)
    opcode, op_name = meta.get(name, ("", ""))
    if not opcode:
        base = name.split(".")[0]
        opcode = base if base in PERMUTE_OPCODES else ""
    if opcode in CONTAINER_OPCODES:
        return "container"
    return layer_of(opcode, op_name)
