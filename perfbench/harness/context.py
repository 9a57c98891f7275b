"""What a loop gets (``Context``) and gives back (``Outcome``)."""
from __future__ import annotations

import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import jax

from harness.spec import Cell
from harness.tracing import TraceSummary

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's events.
    One instance per process (``counter()``): JAX keeps its listeners."""

    _instance = None

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def counter(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_loads += 1

    def total(self) -> int:
        return self.compiles + self.cache_loads


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float          # time.monotonic() when the process started
    on_chip: bool
    device_kind: str
    work_dir: Path          # <checkout>/.perfbench/<workload>, fresh
    compiles: Optional[CompileCounter] = None

    @property
    def scratch(self) -> Path:
        return self.work_dir / "ckpt"

    @property
    def trace_dir(self) -> Path:
        return self.work_dir / "trace"

    def setup_s(self) -> float:
        return time.monotonic() - self.t_start

    @staticmethod
    def log(msg: str) -> None:
        print(f"[perfbench] {msg}", file=sys.stderr, flush=True)

    def fresh_dirs(self) -> None:
        shutil.rmtree(self.work_dir, ignore_errors=True)
        self.scratch.mkdir(parents=True)


@dataclass
class Outcome:
    e2e: dict                      # end-to-end metric -> value
    checks: dict                   # number compared -> (value, limit)
    attempted: int
    failed: int
    record: dict                   # what the metric readers read
    memory_peak_bytes: int
    window_compiles: int
    trace: Optional[TraceSummary] = None

    @property
    def correct(self) -> bool:
        # a NaN compares False, so it fails its limit
        return all(v <= lim for v, lim in self.checks.values())


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no such count)."""
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
