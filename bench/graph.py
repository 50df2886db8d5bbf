"""The gossip graph a traffic file names, in one place: the reference's
mixing weights and the senders the mix kernel's bytes count both follow
from it.  A topology the benchmark does not know is refused."""
from __future__ import annotations

import numpy as np

TOPOLOGIES = ("ring",)


def weights(topology: str, n: int) -> np.ndarray:
    """The mixing matrix W of ``n`` nodes.  The ring gives weight 1/3 to a
    node and to each of its two neighbours (two nodes: 1/2 each; one node:
    W = 1)."""
    if topology not in TOPOLOGIES:
        raise ValueError(f"unknown topology {topology!r}; the benchmark "
                         f"knows {TOPOLOGIES}")
    if n == 1:
        return np.ones((1, 1))
    if n == 2:
        return np.full((2, 2), 0.5)
    W = np.zeros((n, n))
    for i in range(n):
        for j in (i - 1, i, i + 1):
            W[i, j % n] += 1.0 / 3.0
    return W


def hops(topology: str, n: int) -> int:
    """Neighbours node 0 hears from: the other senders in its row of W."""
    return int(np.count_nonzero(weights(topology, n)[0])) - 1
