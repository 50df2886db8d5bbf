"""The program's own host spans in a traced window, and the idle gaps they
name.

``TrainerRunner.step`` marks each call as ``repro.step`` (a
``StepTraceAnnotation`` numbered by the runner's count of dispatched
steps), with the children ``repro.step.place`` (the batch's
``device_put``) and ``repro.step.call`` (the executable call).  The
profiler writes them into the same xplane as the device ops and the
harness's ``bench.*`` phases, on the same clock.  ``bench/trace.py``'s
table keeps the ``bench.*`` phases alone; this module reads the program's
spans beside them.  A program that writes no span gives an empty list,
and a reader of it finds nothing to read.

  python bench/spans.py bench_out/trace/<cell>

prints the traced window's longest idle gaps, each named by the host phase
that overlaps it most and, where a program span overlaps it too, by the
innermost such span: ``<bench phase>/<repro span>``.
"""
from __future__ import annotations

import os
import statistics
import sys
from typing import List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace as traces  # noqa: E402

STEP = "repro.step"
PREFIX = "repro."
# where bench/run.py writes a cell's traced window: <TRACES>/<cell>
TRACES = os.path.join(ROOT, "bench_out", "trace")

Span = Tuple[str, int, int]       # (name, start_ns, dur_ns)


def read_spans(path: str) -> List[Span]:
    """The ``repro.*`` events of an xplane's host planes."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.duration_ns)))
    return out


def of_cell(cell_name: str) -> List[Span]:
    """The program spans of a cell's last traced window; [] where there is
    no trace."""
    path = traces.find_xplane(os.path.join(TRACES, cell_name))
    return read_spans(path) if path else []


def step_ms(spans: Sequence[Span]) -> Optional[float]:
    """Median host duration of a ``repro.step`` span (ms); None where
    there is none."""
    durs = [d for name, _, d in spans if name == STEP]
    return statistics.median(durs) / 1e6 if durs else None


def _overlap(s: int, e: int, span: Span) -> int:
    return min(e, span[1] + span[2]) - max(s, span[1])


def _most_overlap(s: int, e: int, spans: Sequence[Span]) -> Optional[str]:
    """The name of the span that overlaps [s, e) most, or None."""
    best, over = None, 0
    for span in spans:
        if _overlap(s, e, span) > over:
            best, over = span[0], _overlap(s, e, span)
    return best


def _innermost(s: int, e: int, spans: Sequence[Span]) -> Optional[str]:
    """The name of the innermost span over [s, e): the span that overlaps
    it most, then, while spans that overlap it lie inside that one, the
    one of them that overlaps it most."""
    left = [sp for sp in spans if _overlap(s, e, sp) > 0]
    best = None
    while True:
        inside = [sp for sp in left if best is None or (
            best[1] <= sp[1] and sp[1] + sp[2] <= best[1] + best[2])]
        if not inside:
            return best[0] if best else None
        best = max(inside, key=lambda sp: (_overlap(s, e, sp), -sp[2]))
        left.remove(best)


def idle_gaps(table: traces.Table, spans: Sequence[Span],
              n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of ``table`` as [name, seconds], as in
    ``Table.breakdown``, each named ``<bench phase>/<program span>`` where
    a program span overlaps it and by the bench phase alone where none
    does."""
    gaps = sorted(((e - s, s, e) for d in table.devices
                   for s, e in table.gaps(d)), reverse=True)[:n]
    out = []
    for dur, s, e in gaps:
        name = _most_overlap(s, e, table.host) or "no host phase"
        inner = _innermost(s, e, spans)
        out.append([f"{name}/{inner}" if inner else name, dur / 1e9])
    return out


def main(argv=None) -> int:
    trace_dir = (argv if argv is not None else sys.argv[1:])[0]
    path = traces.find_xplane(trace_dir)
    if path is None:
        print(f"no trace under {trace_dir}", file=sys.stderr)
        return 2
    from jax.profiler import ProfileData
    ids = sorted(int(p.name.rsplit(":", 1)[1])
                 for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:"))
    ops, host = traces.read_xplane(path, ids)
    table = traces.table_from(ops, host, tuple(ids))
    spans = read_spans(path)
    print(f"{len(spans)} program spans; repro.step median "
          f"{step_ms(spans)} ms")
    for name, seconds in idle_gaps(table, spans):
        print(f"{seconds!r} s  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
