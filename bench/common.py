"""What every driver shares: the run's inputs, its result, checks, tracing."""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import shutil
import time
from typing import Any

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench_traces"          # gitignored; emptied per trace


@dataclasses.dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    devices: list
    peaks: dict
    t_process: float

    @property
    def chips(self) -> int:
        return len(self.devices)


@dataclasses.dataclass
class Check:
    """One compared number: ``ok`` iff value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Layer:
    """What the per-layer readers read: traces, counters, shapes, peaks."""

    window: Any                 # trace.Summary of the traced window
    probes: Any                 # trace.Summary of the probe calls, or None
    counters: dict
    config: dict
    peaks: dict
    chips: int


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    e2e: dict
    checks: list
    memory_peak_bytes: int
    layer: Layer | None = None


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of the devices (0 if unreported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@contextlib.contextmanager
def profiled(name: str):
    """Trace the block with JAX's profiler into ``TRACE_DIR/name``.

    Host spans are the benchmark's own ``TraceAnnotation``s (host tracer
    level 1); the Python tracer is off. Yields the directory.
    """
    import jax
    path = TRACE_DIR / name
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def now() -> float:
    return time.perf_counter()
