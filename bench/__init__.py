"""Chip benchmark of the decentralized Prox-LEAD trainer.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Everything
that belongs to one configuration, traffic mix, limit set or per-layer
metric lives in a file of its own, found by name:

  configs/<config>.json     model sizes as run, with source and cuts
  traffic/<traffic>.json    nodes, mesh, gossip, compressor, batch, tokens
  limits/<cell>.json        the limit of each number ``correct`` compares
  metrics/<metric>.py       one reader per per-layer metric
  reference/<name>.py       a model kind, named by a configuration's
                            ``reference`` key: shapes, counts, program
                            mapping, reference (bench/kinds.py)
  reference/prox_lead.py    the plain Prox-LEAD reference over any kind
"""
