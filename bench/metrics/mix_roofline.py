"""Unpack+dequant+mix kernel: the bytes the algorithm needs per step
(bench/counts.py) at the chip's HBM peak, over the kernel's device time
per step (%)."""
from bench import counts, graph, peaks


def read(ctx):
    tr = ctx.cell["traffic_params"]
    s = ctx.layer_s_per_step("mix")
    if tr["compressor"] != "qinf" or s is None:
        return None
    need = counts.mix_bytes(ctx.cell["cfg"], tr["bits"], tr["block"],
                            graph.hops(tr["topology"], tr["nodes"]))
    return 100.0 * need / peaks.peaks_for(ctx.device_kind).hbm_bw / s
