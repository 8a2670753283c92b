"""One run of one cell: the set-up clock, the measured window, the traced
part of it, the numbers compared with their limits, and the result line.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import os
import shutil
import sys
import tempfile
import time

from . import registry


def process_start() -> float:
    """The ``perf_counter`` reading at this process's start, from /proc
    (10 ms resolution); now, where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def limits(workload: str, bench_dir=registry.BENCH) -> dict:
    """The limit of each number this cell compares."""
    return registry.load_json(bench_dir / "limits" / f"{workload}.json")


class _Compiles:
    """Counts JAX's tracing and compiling events while ``on``; one
    listener per process."""
    _one = None

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        cls._one.events = {}
        return cls._one

    def _hear(self, event, duration, **_):
        if self.on and ("compile" in event or "trace" in event):
            self.events[event] = self.events.get(event, 0) + 1


class _Pauses:
    """Python's garbage collections while ``on``: count and seconds."""
    _one = None

    def __init__(self):
        self.on = False
        self.times: list[float] = []
        self._t = None
        gc.callbacks.append(self._hear)

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        cls._one.times = []
        return cls._one

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on and self._t is not None:
            self.times.append(time.perf_counter() - self._t)


class Run:
    """What a driver needs from the harness, and what it hands back.

    A driver makes its inputs and the system under test, warms every
    shape, then calls ``open_window()``; it drives the system until
    ``now() >= t_close`` (calling ``poll()`` as it goes), calls
    ``close_window()``, frees the system, and adds each compared number
    with ``check``. It records the traced part's counts in ``facts``."""

    def __init__(self, *, workload, cfg, traffic, seed, seconds, trace,
                 t_start, limits):
        self.workload, self.cfg, self.traffic = workload, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.limits = limits
        self.control = False            # also read the precision control
        self.control_readings: dict = {}
        self.checks: list = []          # (name, value, limit)
        self.facts: dict = {}
        self.spans: list = []           # program spans in the traced part
        self.setup_s = None
        self.memory_peak = 0
        self.t_open = self.t_close = self.t_trace_close = None
        # the longest time between two polls in the window: a stall of
        # the host, or a wait on the device, shows here
        self.longest_gap = 0.0
        self._last_poll = None
        self._tracing = False
        self._trace_dir = None
        self._window_span = None
        self._compiles = _Compiles.get()
        self._pauses = _Pauses.get()

    now = staticmethod(time.perf_counter)

    def annotate(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def open_window(self) -> float:
        import jax
        # what set-up made lives on: the window's collections leave it
        # unscanned, as a server frozen after start-up does
        gc.collect()
        gc.freeze()
        self.setup_s = self.now() - self.t_start
        if self.trace:
            from repro.obs import trace as obs_trace
            obs_trace.enable()
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        self._compiles.on = self._pauses.on = True
        self.t_open = self._last_poll = self.now()
        self.t_close = self.t_open + self.seconds
        self._trace_until = self.t_open + min(
            self.seconds, self.traffic.get("trace_seconds", self.seconds))
        return self.t_open

    def poll(self, now: float | None = None):
        now = now or self.now()
        self.longest_gap = max(self.longest_gap, now - self._last_poll)
        self._last_poll = now
        if self._tracing and now >= self._trace_until:
            self._stop_trace()

    def trace_window(self) -> tuple[float, float]:
        return self.t_open, self.t_trace_close or self.t_close

    def _stop_trace(self):
        import jax
        self._window_span.__exit__(None, None, None)
        self.t_trace_close = self.now()
        jax.profiler.stop_trace()
        self._tracing = False
        from repro.obs import trace as obs_trace
        tracer = obs_trace.get_tracer()
        epoch = tracer._epoch
        t0, t1 = self.trace_window()
        for ev in tracer.events:
            ts = epoch + ev["ts"] * 1e-6
            if t0 <= ts < t1:
                self.spans.append({"name": ev["name"], "t": ts,
                                   "dur": ev.get("dur", 0) * 1e-6,
                                   "args": ev.get("args", {})})
        obs_trace.disable()

    def close_window(self):
        import jax
        if self._tracing:
            self._stop_trace()
        self._compiles.on = self._pauses.on = False
        gc.unfreeze()
        # the fullest chip's peak; backends that keep no statistics give 0
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices())

    @property
    def compiles_in_window(self) -> dict:
        return dict(self._compiles.events)

    @property
    def gc_in_window(self) -> tuple[int, float]:
        """Python's collections in the window: (count, longest seconds)."""
        return len(self._pauses.times), max(self._pauses.times, default=0.0)

    def check(self, name: str, value: float):
        self.checks.append((name, float(value), float(self.limits[name])))

    def trace_file(self):
        if self._trace_dir is None:
            return None
        files = glob.glob(os.path.join(self._trace_dir, "**",
                                       "*.xplane.pb"), recursive=True)
        return files[0] if files else None

    def cleanup(self):
        if self._trace_dir:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


class MetricContext:
    """What a per-layer reader sees: the reduced trace (or None), the
    program's spans and the harness's counts in the traced window, the
    configuration, the traffic and the chip's peaks."""

    def __init__(self, run: Run, reduced, peaks: dict):
        self.trace = reduced
        self.spans = run.spans
        self.facts = run.facts
        self.cfg = run.cfg
        self.traffic = run.traffic
        self.peaks = peaks


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)
