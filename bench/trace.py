"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

The JAX profiler writes one ``.xplane.pb`` per traced window.  On a TPU it
holds one plane per chip (``/device:TPU:<n>``) with the lines

* ``XLA Modules``: one event per program run, named ``jit_<fn>(<hash>)``;
* ``XLA Ops``: one event per operation; a Pallas (Mosaic) kernel is a
  ``custom-call`` operation inside its program;

and a host plane (``/host:CPU``) whose ``python`` line carries the
harness's ``TraceAnnotation`` spans (``bench.window``, ``bench.explore``,
``bench.submit``, ``bench.flush``).  Host and device events share one
clock (nanoseconds from the start of the trace).

A trace kept for tests may be gzipped (``.xplane.pb.gz``).
``reduce(path)`` returns a ``Summary``: the traced window, per device the
busy intervals (the union of its operations), idle share, device time per
program and per kernel, and the breakdown the result line carries.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
KERNEL_MARK = "custom-call("
_PROGRAM = re.compile(r"^(.*?)\(\d+\)$")


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float
    programs: dict  # program name -> device seconds
    kernels: dict  # program name -> seconds of its custom-call kernels
    ops: dict  # operation name -> device seconds
    gaps: list  # (start_ns, end_ns) idle gaps inside the window


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: list
    spans: dict  # harness span name -> [(start_ns, end_ns)]
    _host: list = dataclasses.field(default=None, repr=False)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_s(self, prefix: str) -> float:
        """Device seconds of programs whose name starts with ``prefix``,
        summed over devices."""
        return sum(
            s for d in self.devices for n, s in d.programs.items()
            if n.startswith(prefix)
        )

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of the kernels inside programs named ``prefix``*,
        summed over devices."""
        return sum(
            s for d in self.devices for n, s in d.kernels.items()
            if n.startswith(prefix)
        )

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed over
        devices) and the idle time by what the host was doing."""
        ops: dict = {}
        for d in self.devices:
            for n, s in d.ops.items():
                ops[n] = ops.get(n, 0.0) + s
        gaps: dict = {}
        for d in self.devices:
            for a, b in d.gaps:
                label = self.host_label((a + b) / 2)
                gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
        return {
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
        }

    def host_label(self, t_ns: float) -> str:
        """The harness span open at ``t_ns`` (the spans inside the window
        do not overlap), else 'bench.idle'."""
        if self._host is None:
            self._host = sorted(
                (a, b, name) for name, spans in self.spans.items()
                for a, b in spans
            )
        return _containing(self._host, t_ns) or "bench.idle"


def xplane_file(log_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb*"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _program(name: str) -> str:
    m = _PROGRAM.match(name)
    return m.group(1) if m else name


def _op(name: str) -> str:
    """A short stable name for an XLA operation event: the instruction's
    name without its number (``%fusion.8 = ...`` -> ``fusion``), and
    ``custom-call`` kernels by their kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"\.\d+$", "", head)
    if KERNEL_MARK in name:
        return f"{base} (custom-call kernel)"
    return base


def reduce(path: str, window: str = WINDOW_SPAN) -> Summary:
    """Summarise the trace at ``path`` (a file or the profiler's log dir)
    over the extent of the harness span ``window``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_file(path)
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    spans: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns)
                    )
    if window not in spans:
        raise ValueError(f"{path}: no {window!r} span in the trace")
    lo = min(a for a, _ in spans[window])
    hi = max(b for _, b in spans[window])

    devices = []
    for plane in data.planes:
        if not re.match(r"^/device:(TPU|GPU):\d+$", plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = [
            (e.start_ns, e.start_ns + e.duration_ns, _program(e.name))
            for e in lines.get("XLA Modules", ())
        ]
        ops = [
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in lines.get("XLA Ops", ())
        ]
        ops = [(a, b, n) for a, b, n in ops if b > lo and a < hi]
        if not ops:
            continue
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        programs: dict = {}
        for a, b, n in modules:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                programs[n] = programs.get(n, 0.0) + (b - a) * 1e-9
        kernels: dict = {}
        mod_sorted = sorted(modules)
        op_time: dict = {}
        for a, b, n in ops:
            dur = (min(b, hi) - max(a, lo)) * 1e-9
            key = _op(n)
            op_time[key] = op_time.get(key, 0.0) + dur
            if KERNEL_MARK in n:
                owner = _containing(mod_sorted, a)
                if owner is not None:
                    kernels[owner] = kernels.get(owner, 0.0) + dur
        devices.append(Device(
            name=plane.name,
            busy_s=sum(b - a for a, b in busy) * 1e-9,
            programs=programs, kernels=kernels, ops=op_time, gaps=gaps,
        ))
    if not devices:
        raise ValueError(f"{path}: no device operation inside the window")
    return Summary(window_s=(hi - lo) * 1e-9, devices=devices,
                   spans={k: v for k, v in spans.items() if k != window})


def _containing(intervals, t_ns):
    """Name of the interval of sorted, disjoint ``(start, end, name)``
    that contains ``t_ns``, or None."""
    i = bisect.bisect_right(intervals, (t_ns, float("inf"), "")) - 1
    if i >= 0 and intervals[i][0] <= t_ns < intervals[i][1]:
        return intervals[i][2]
    return None
