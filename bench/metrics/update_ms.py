"""Prox-LEAD update: device ms per step of every op that is neither
forward+backward, nor one of the two wire kernels, nor a collective-permute:
the update's elementwise work, the stochastic-rounding noise, the relayouts
around the kernels and the consensus metric."""


def read(ctx):
    s = ctx.layer_s_per_step("other")
    return None if s is None else 1e3 * s
