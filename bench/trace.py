"""The profiler's trace of a window, read into a table of device ops.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with JAX alone.  Each TPU is a plane
``/device:TPU:<id>``; its ops are the events of the line ``XLA Ops``.  The
host's own phases are the ``bench.*`` TraceAnnotation events of the host
plane.  Which op belongs to which layer is decided by ``bench/layers.py``."""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import layers

OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start_ns: int
    dur_ns: int


@dataclasses.dataclass
class Table:
    ops: List[Op]
    host: List[Tuple[str, int, int]]      # (name, start_ns, dur_ns)
    devices: Tuple[int, ...]
    window_s: float

    def busy_ns(self, device: int) -> int:
        """Union of the op intervals on one device."""
        ops = [o for o in self.ops if o.device == device]
        if not ops:
            return 0
        span = (max(o.start_ns + o.dur_ns for o in ops)
                - min(o.start_ns for o in ops))
        return span - sum(e - s for s, e in self.gaps(device))

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices traced."""
        return (sum(self.busy_ns(d) for d in self.devices)
                / len(self.devices) / 1e9)

    def gaps(self, device: int) -> List[Tuple[int, int]]:
        """Idle intervals (start, end) between ops on one device."""
        iv = sorted((o.start_ns, o.start_ns + o.dur_ns)
                    for o in self.ops if o.device == device)
        out, end = [], None
        for s, e in iv:
            if end is not None and s > end:
                out.append((end, s))
            end = e if end is None else max(end, e)
        return out

    def breakdown(self, label: Callable[[Op], Optional[str]] = lambda o: o.name,
                  n: int = 10) -> Dict[str, list]:
        """The device ops that took most time (summed by ``label``, which
        returns None for an op to leave out, averaged over devices) and the
        longest idle gaps, each named by the host phase that overlaps it
        most."""
        by_name: Dict[str, int] = {}
        for o in self.ops:
            key = label(o)
            if key is not None:
                by_name[key] = by_name.get(key, 0) + o.dur_ns
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        device_ops = [[k, v / len(self.devices) / 1e9] for k, v in top]
        gaps = sorted(((e - s, s, e) for d in self.devices
                       for s, e in self.gaps(d)), reverse=True)[:n]
        idle = []
        for dur, s, e in gaps:
            best, over = "no host phase", 0
            for name, hs, hd in self.host:
                ov = min(e, hs + hd) - max(s, hs)
                if ov > over:
                    best, over = name, ov
            idle.append([best, dur / 1e9])
        return {"device_ops": device_ops, "idle_gaps": idle}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None




def read_xplane(path: str, device_ids: Sequence[int]) -> Tuple[list, list]:
    """(device ops, host bench.* spans) of one xplane file, on one clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, host = [], []
    want = {f"/device:TPU:{i}" for i in device_ids}
    for plane in pd.planes:
        if plane.name in want:
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(dev, layers.instruction(ev.name),
                                  int(ev.start_ns), int(ev.duration_ns)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    return ops, host


def load(trace_dir: str, devices) -> Table:
    path = find_xplane(trace_dir)
    ids = tuple(d.id for d in devices)
    ops, host = read_xplane(path, ids) if path else ([], [])
    return table_from(ops, host, ids)


def table_from(ops, host, ids) -> Table:
    """The traced window runs from the first host phase to the end of the
    fence (or of the last op, where no phase was recorded)."""
    starts = [s for _, s, _ in host] + [o.start_ns for o in ops]
    ends = [s + d for _, s, d in host] + [o.start_ns + o.dur_ns for o in ops]
    window = (max(ends) - min(starts)) / 1e9 if starts else 0.0
    return Table(ops=ops, host=host, devices=ids, window_s=window)


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read: the cell, the traced window's
    op table, its steps and tokens, its host-clock seconds, and the
    compiled step's HLO text."""
    cell: dict
    table: Table
    steps: int
    tokens: int
    window_s: float
    chips: int
    device_kind: str
    hlo_text: Callable[[], str]
    _meta: Optional[dict] = None

    def meta(self) -> dict:
        if self._meta is None:
            self._meta = layers.hlo_meta(self.hlo_text())
        return self._meta

    def layer_of(self, op: Op) -> str:
        return layers.classify(op.name, self.meta())

    def breakdown(self) -> Dict[str, list]:
        """The table's breakdown, ops named 'instruction (layer)'."""
        def label(o):
            layer = self.layer_of(o)
            return None if layer == "container" else f"{o.name} ({layer})"
        return self.table.breakdown(label)

    def layer_s_per_step(self, layer: str) -> Optional[float]:
        """Device seconds per step (per chip) of one layer's ops; None
        where the trace holds none of them."""
        meta = self.meta()
        return self._s_per_step(o for o in self.table.ops
                                if layers.classify(o.name, meta) == layer)

    def named_s_per_step(self, name: str) -> Optional[float]:
        """Device seconds per step (per chip) of the ops whose ``op_name``
        holds ``name``, such as a kernel's ``jit(<entry>)``, in whatever
        layer they lie; None where the trace holds none of them."""
        meta = self.meta()
        return self._s_per_step(
            o for o in self.table.ops
            if name in meta.get(o.name, ("", ""))[1]
            and layers.classify(o.name, meta) != "container")

    def _s_per_step(self, ops) -> Optional[float]:
        durs = [o.dur_ns for o in ops]
        if not durs or not self.steps:
            return None
        return sum(durs) / len(self.table.devices) / 1e9 / self.steps
