"""Process-local metric registry: counters, gauges, and the ambient stack.

A :class:`Meters` is a flat ``name -> number`` map with two write verbs:

* ``inc(name, v)``  — counter semantics (wall-clock spans, trace counts);
* ``set(name, v)``  — gauge semantics, **idempotent**: hooks that run at
  jit *trace time* (e.g. ``WireExchange`` computing its static
  ``BucketLayout`` inside ``shard_map``) may re-execute on every retrace,
  so anything recorded from traced code must use ``set``.

Instrumented library code never takes a registry argument — it records
into the *ambient* registry, installed with :func:`using_meters`::

    m = Meters()
    with using_meters(m):
        runner.run(...)          # WireExchange / simulate hooks land in m

With no ambient registry every hook is a no-op (``current_meters()``
returns ``None``), so un-instrumented callers pay nothing.

Naming convention (slash-separated namespaces, units as suffixes):
``time/<span>_s``, ``time/<span>_n``, ``wire/bytes_per_hop``,
``wire/hops``, ``wire/collectives_per_step``, ``wire/traces``,
``netsim/bits_per_edge_per_round``, and the compile counters below.

Compile counters come from ``jax.monitoring``: the first
:func:`using_meters` registers one duration listener and one event
listener for the process, which forward into the ambient registry and do
nothing when none is installed:

* ``compile/backend_s``, ``compile/n`` — every XLA compile
  (``/jax/core/compile/backend_compile_duration``).  That event wraps the
  persistent-cache lookup, so a cache hit's read is counted there and
  nowhere else;
* ``compile/cache_hits``, ``compile/cache_misses`` — the persistent
  compile cache;
* ``compile/step_lower_s``, ``compile/step_n`` — tracing plus MLIR
  lowering of the train step itself (:data:`STEP_FUN`), once per
  lowering.  Jits nested in the step fire trace events of their own
  inside the step's; they are left out, so no second counts twice.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Iterator, List, Optional


class Meters:
    """Flat name -> number registry (thread-safe; see module docstring)."""

    def __init__(self) -> None:
        self._values: Dict[str, float] = {}
        self._lock = threading.Lock()

    def inc(self, name: str, value: float = 1) -> None:
        """Counter write: add ``value`` to ``name`` (0 if absent)."""
        with self._lock:
            self._values[name] = self._values.get(name, 0) + value

    def set(self, name: str, value: float) -> None:
        """Gauge write: assign ``value`` (idempotent — safe at trace time)."""
        with self._lock:
            self._values[name] = value

    def get(self, name: str, default: float = 0) -> float:
        with self._lock:
            return self._values.get(name, default)

    def as_dict(self) -> Dict[str, float]:
        """Sorted plain-dict snapshot (JSON-ready)."""
        with self._lock:
            return {k: self._values[k] for k in sorted(self._values)}

    def clear(self) -> None:
        with self._lock:
            self._values.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._values)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Meters({self.as_dict()!r})"


# --------------------------------------------------------------------------
# Ambient registry stack
# --------------------------------------------------------------------------

_STACK: List[Meters] = []
_STACK_LOCK = threading.Lock()


def current_meters() -> Optional[Meters]:
    """The innermost registry installed by :func:`using_meters`, or None."""
    with _STACK_LOCK:
        return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def using_meters(meters: Meters) -> Iterator[Meters]:
    """Install ``meters`` as the ambient registry for the with-block."""
    _listen_to_compiles()
    with _STACK_LOCK:
        _STACK.append(meters)
    try:
        yield meters
    finally:
        with _STACK_LOCK:
            _STACK.remove(meters)


# --------------------------------------------------------------------------
# Compile counters (see module docstring)
# --------------------------------------------------------------------------

# The function TrainerRunner jits: DecentralizedTrainer.train_step.
STEP_FUN = "train_step"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# (event, fun_name) of the train step's own tracing and lowering
_STEP_LOWERING = {(_TRACE, STEP_FUN), (_LOWER, f"jit({STEP_FUN})")}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile/cache_hits",
                 "/jax/compilation_cache/cache_misses":
                     "compile/cache_misses"}
_listening = False


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    m = current_meters()
    if m is None:
        return
    if event == _BACKEND_COMPILE:
        m.inc("compile/backend_s", duration_s)
        m.inc("compile/n")
    elif (event, kwargs.get("fun_name")) in _STEP_LOWERING:
        m.inc("compile/step_lower_s", duration_s)
        if event == _TRACE:
            m.inc("compile/step_n")


def _on_event(event: str, **kwargs) -> None:
    m = current_meters()
    if m is not None and event in _CACHE_EVENTS:
        m.inc(_CACHE_EVENTS[event])


def _listen_to_compiles() -> None:
    """Register the compile listeners, once per process."""
    global _listening
    with _STACK_LOCK:
        if _listening:
            return
        _listening = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


# --------------------------------------------------------------------------
# Environment stamp
# --------------------------------------------------------------------------

def env_info() -> Dict[str, object]:
    """The environment block stamped into every RunReport / BENCH file:
    enough to attribute a perf-history record to a machine class."""
    import jax
    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count() or 1,
        "x64": bool(jax.config.jax_enable_x64),
    }
