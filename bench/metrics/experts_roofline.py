"""Held experts' grouped matmul: the FLOPs its work needs per step at the
balanced load (the model kind's ``held_expert_flops_per_token``, forward
and backward) at the chip's bf16 peak, over the device time of its ops:
those under the program's scope ``moe/experts``, and the TPU's ragged-dot
kernels, to which the compiler gives the ``op_name`` ``ragged-dot-*`` in
place of the name stack (%).  The count is the same whatever implements
the matmuls; a kind without held experts, or a program without the
scope, reads nothing."""
from bench import kinds, peaks, scopes

RAGGED_DOT = "ragged-dot"


def read(ctx):
    count = getattr(kinds.of(ctx.cell["cfg"]), "held_expert_flops_per_token",
                    None)
    ms = scopes.part_ms(ctx, "moe/experts", "fwd_bwd")
    if count is None or ms is None or not ctx.steps:
        return None
    s = ms / 1e3 + (ctx.named_s_per_step(RAGGED_DOT) or 0.0)
    flops = count(ctx.cell["cfg"]) * ctx.tokens / ctx.steps / ctx.chips
    return 100.0 * flops / peaks.peaks_for(ctx.device_kind).flops / s
