"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metric
readers take: the device-op intervals of each TPU inside the measured
window, and the benchmark's own host spans on the same clock.

The window is the host span ``bench.window`` (a ``TraceAnnotation``
that the harness opens around the measured loop).  Device ops are the
events of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; busy
time is the union of their intervals.  Host spans are the events whose
name starts with ``bench.`` on any ``/host:`` plane.

    python bench/trace_reduce.py <file.xplane.pb>    # prints a summary
"""

from __future__ import annotations

import dataclasses
import gzip
import re
import sys

import numpy as np

from stats import union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def short_name(hlo: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's own name (``%while.110 = (...) while(...)`` ->
    ``while.110``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class DeviceOps:
    names: list[str]          # one per event
    start: np.ndarray         # float64 ns
    end: np.ndarray           # float64 ns


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]                  # ns
    devices: dict[int, DeviceOps]                # TPU index -> ops
    spans: list[tuple[str, float, float]]        # (name, start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([union_seconds(d.start, d.end)
                              for d in self.devices.values()]))

    def op_seconds(self, pattern: str | None = None) -> dict[str, float]:
        """Summed device seconds per op name (all devices), optionally
        only the names matching the regex ``pattern``."""
        rx = re.compile(pattern) if pattern else None
        out: dict[str, float] = {}
        for d in self.devices.values():
            for name, s, e in zip(d.names, d.start, d.end):
                if rx is None or rx.search(name):
                    out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def idle_by_span(self) -> dict[str, float]:
        """Idle device seconds inside the window (averaged over the
        devices), attributed to the host span that was open at the
        time; idle time under no leaf span is ``unspanned``."""
        if not self.devices:
            return {}
        leaf = [(n, s, e) for n, s, e in self.spans if n != WINDOW_SPAN]
        totals: dict[str, float] = {}
        for d in self.devices.values():
            gs, ge = _gaps(d.start, d.end, *self.window)
            cum = np.concatenate([[0.0], np.cumsum(ge - gs)])

            def before(t):
                t = np.asarray(t, dtype=np.float64)
                i = np.searchsorted(gs, t, side="right") - 1
                ok = i >= 0
                ic = np.maximum(i, 0)
                part = np.clip(t - gs[ic], 0.0, ge[ic] - gs[ic])
                return np.where(ok, cum[ic] + part, 0.0)

            idle = cum[-1]
            spanned = 0.0
            if leaf:
                names = np.array([n for n, _, _ in leaf])
                s = np.array([x for _, x, _ in leaf])
                e = np.array([x for _, _, x in leaf])
                got = before(e) - before(s)
                for name in np.unique(names):
                    v = float(got[names == name].sum())
                    totals[name] = totals.get(name, 0.0) + v / 1e9
                    spanned += v
            totals["unspanned"] = (totals.get("unspanned", 0.0)
                                   + (idle - spanned) / 1e9)
        k = len(self.devices)
        return {n: v / k for n, v in totals.items()}

    def span_seconds(self, name: str) -> list[float]:
        return [(e - s) / 1e9 for n, s, e in self.spans if n == name]


def _gaps(start, end, w0, w1):
    """Sorted disjoint idle intervals of the ops inside [w0, w1]."""
    s = np.clip(np.asarray(start, np.float64), w0, w1)
    e = np.clip(np.asarray(end, np.float64), w0, w1)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e) if e.size else e
    prev_end = np.concatenate([[w0], reach])
    next_start = np.concatenate([s, [w1]])
    keep = next_start > prev_end
    return prev_end[keep], next_start[keep]


def load(source) -> "object":
    """ProfileData from a path (``.xplane.pb`` or ``.xplane.pb.gz``)
    or from serialized bytes."""
    from jax.profiler import ProfileData

    if isinstance(source, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(source))
    path = str(source)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce(source) -> Reduced:
    pd = load(source)
    devices: dict[int, DeviceOps] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            names, st, en = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    names.append(short_name(ev.name))
                    st.append(ev.start_ns)
                    en.append(ev.end_ns)
            devices[int(m.group(1))] = DeviceOps(
                names, np.asarray(st, np.float64), np.asarray(en, np.float64))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.end_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    w0, w1 = windows[0]
    for d, ops in devices.items():
        inside = (ops.end > w0) & (ops.start < w1)
        devices[d] = DeviceOps(
            [n for n, k in zip(ops.names, inside) if k],
            np.clip(ops.start[inside], w0, w1),
            np.clip(ops.end[inside], w0, w1),
        )
    spans = sorted((x for x in spans if x[2] > w0 and x[1] < w1),
                   key=lambda x: x[1])
    return Reduced((w0, w1), devices, spans)


def summarize(source) -> None:
    """Print every plane and line with event counts, and a few events
    with their stats: the look by hand before code reads a trace."""
    pd = load(source)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:3]:
                stats = dict(ev.stats) if ev.stats else {}
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}")
            total: dict[str, float] = {}
            for ev in evs:
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
            if len(total) > 3:
                top = sorted(total.items(), key=lambda x: -x[1])[:15]
                print(f"    top by time: {top}")


if __name__ == "__main__":
    summarize(sys.argv[1])
