"""bench/graph.py: the one place the gossip graph of a traffic file is
read, and a topology the benchmark does not know is refused."""
import json
import os

import numpy as np
import pytest

from _bench_path import ROOT
from bench import graph, run


@pytest.mark.parametrize("n,hops", [(1, 0), (2, 1), (3, 2), (4, 2), (8, 2)])
def test_ring_rows_are_stochastic_with_their_hops(n, hops):
    W = graph.weights("ring", n)
    assert W.shape == (n, n)
    np.testing.assert_allclose(W.sum(axis=1), 1.0)
    np.testing.assert_allclose(W, W.T)
    assert graph.hops("ring", n) == hops


@pytest.mark.parametrize("name", ["exponential", "complete", ""])
def test_unknown_topology_is_refused(name):
    with pytest.raises(ValueError, match="unknown topology"):
        graph.weights(name, 4)
    with pytest.raises(ValueError, match="unknown topology"):
        graph.hops(name, 4)


def test_every_traffic_file_names_a_known_topology():
    d = os.path.join(ROOT, "bench", "traffic")
    for f in sorted(os.listdir(d)):
        with open(os.path.join(d, f)) as fh:
            tr = json.load(fh)
        assert graph.weights(tr["topology"], tr["nodes"]).shape[0] \
            == tr["nodes"], f


def test_load_cell_refuses_an_unknown_topology(monkeypatch):
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    name = manifest["workloads"][0]["name"]
    real = run.load_json

    def load_json(path):
        out = real(path)
        if os.sep + "traffic" + os.sep in path:
            out = dict(out, topology="exponential")
        return out
    monkeypatch.setattr(run, "load_json", load_json)
    with pytest.raises(run.BenchError, match="unknown topology"):
        run.load_cell(name, manifest)
