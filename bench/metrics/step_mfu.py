"""Whole step: model FLOPs of the traced window's tokens over its
host-clock seconds, as a share of the chips' bf16 peak (%)."""
from bench import counts, peaks


def read(ctx):
    if not ctx.tokens or ctx.window_s <= 0:
        return None
    tr = ctx.cell["traffic_params"]
    flops = counts.flops_per_token(ctx.cell["cfg"], tr["seq_len"]) * ctx.tokens
    peak = peaks.peaks_for(ctx.device_kind).flops * ctx.chips
    return 100.0 * flops / ctx.window_s / peak
