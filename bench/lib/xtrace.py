"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the traced window, the device's busy intervals, every device
operation with the program (XLA module) it ran in, and the idle gaps with
the host span that covered each.

The window is the host span named ``bench.window``, which the harness
opens and closes around the traced part of its measured window. Device
operations are the events of the ``XLA Ops`` line of each ``/device:``
plane, modules those of its ``XLA Modules`` line. Only JAX's own reader
(``jax.profiler.ProfileData``) is used.
"""
from __future__ import annotations

import dataclasses

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str
    start: int          # ns, the trace's clock
    dur: int            # ns
    module: str
    device: str
    detail: str = ""    # the op's naming statistics (HLO text, framework op)

    @property
    def label(self) -> str:
        """Name and naming statistics, what kernel names are matched on."""
        return f"{self.name} {self.detail}"


@dataclasses.dataclass
class Reduced:
    t0: int
    t1: int
    devices: list
    ops: list            # [Op] clipped to nothing: whole ops in the window
    busy: dict           # device → busy ns inside the window
    gaps: list           # [(start ns, dur ns)] idle on the first device
    host: list           # [(name, start ns, dur ns)] overlapping the window

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(self.busy.values()) / max(len(self.busy), 1) * 1e-9

    def op_seconds(self, match) -> float:
        """Summed device seconds of the ops whose label ``match``
        accepts."""
        return sum(o.dur for o in self.ops if match(o.label)) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        tot: dict[str, int] = {}
        for o in self.ops:
            key = f"{o.module}/{o.name}"
            tot[key] = tot.get(key, 0) + o.dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def top_gaps(self, n: int = 10) -> list:
        """The n longest idle gaps, each named by the innermost host span
        that covered its middle."""
        top = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return [[f"{_covering(self.host, s + d // 2)} @ "
                 f"{(s - self.t0) * 1e-9:.6f}s", d * 1e-9] for s, d in top]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, t0, t1) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


# op statistics that name what an op runs (its HLO text, framework op or
# kernel); a reader matches kernel names against these and the op's name
_DETAIL_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details")


def _events(line):
    return [(e.name, int(e.start_ns), int(e.duration_ns))
            for e in line.events]


def _ops(line):
    """(name, start, duration, naming statistics) of each op."""
    return [(e.name, int(e.start_ns), int(e.duration_ns),
             " ".join(str(v) for k, v in e.stats if k in _DETAIL_STATS))
            for e in line.events]


def read(path) -> dict:
    """Raw events of a trace file: {"host": [(name, start, dur)],
    "devices": {plane: {"ops": [(name, start, dur, detail)],
    "modules": [(name, start, dur)]}}}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                mods = lines.get(MODULES_LINE)
                devices[plane.name] = {
                    "ops": _ops(lines[OPS_LINE]),
                    "modules": _events(mods) if mods is not None else []}
    return {"host": host, "devices": devices}


def _module_of(modules, start):
    """Name of the module event that covers ``start`` (modules sorted)."""
    lo, hi = 0, len(modules) - 1
    while lo <= hi:
        mid = (lo + hi) // 2
        name, s, d = modules[mid]
        if start < s:
            hi = mid - 1
        elif start >= s + d:
            lo = mid + 1
        else:
            return name
    return "?"


def _covering(host, t):
    """Innermost host span (other than the window) that covers ``t``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and name != WINDOW_SPAN:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "no host span"


def reduce(raw: dict, window_span: str = WINDOW_SPAN) -> Reduced:
    wins = [(s, s + d) for name, s, d in raw["host"] if name == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in the trace")
    t0, t1 = wins[0]
    ops, busy, gaps = [], {}, []
    devices = sorted(raw["devices"])
    for dev in devices:
        planes = raw["devices"][dev]
        mods = sorted(planes["modules"], key=lambda m: m[1])
        ops += [Op(n, s, d, _module_of(mods, s), dev, *rest)
                for n, s, d, *rest in planes["ops"]
                if s >= t0 and s + d <= t1]
        merged = union(clip([(s, s + d) for _, s, d, *_ in planes["ops"]],
                            t0, t1))
        busy[dev] = sum(e - s for s, e in merged)
        if dev == devices[0]:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps = [(s, e - s) for s, e in zip(edges[::2], edges[1::2])
                    if e > s]
    host = [h for h in raw["host"] if h[1] < t1 and h[1] + h[2] > t0]
    return Reduced(t0, t1, devices, ops, busy, gaps, host)
