"""Forward+backward: device ms per step of the ops whose name stack puts
them under the model's jvp or its transpose."""


def read(ctx):
    s = ctx.layer_s_per_step("fwd_bwd")
    return None if s is None else 1e3 * s
