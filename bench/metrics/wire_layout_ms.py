"""Wire buffer layout: device ms per step of the ops of ``update_ms``
under the program's scope ``prox_lead/wire_layout``: blocking, byte
planes, concatenates and slices between the leaves and the two wire
buffers, around the wire kernels."""
from bench import scopes


def read(ctx):
    return scopes.update_part_ms(ctx, scopes.WIRE_LAYOUT)
