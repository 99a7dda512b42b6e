"""Reduce a JAX profiler trace (``.xplane.pb``) by the program's own
names: a second pass over the file ``trace_reduce`` reads.

* Host spans: the program's stages (``quiver.<stage>``, opened by its
  ``Tracer.span``) and the harness's own (``bench.<name>``), on any
  ``/host:`` plane, inside the ``bench.window`` span.
* Modules: each device's ``XLA Modules`` events, one per program run.
* Leaf ops: the ``XLA Ops`` events that enclose no other event on their
  line (a ``while`` holds its body's ops and is a container), each with
  the module run it falls in; ``PlanCache.op_scopes`` maps their names
  to the program's ``quiver.*`` named scopes.
* Idle: device idle inside the window, attributed to the innermost
  ``quiver.*`` or ``bench.*`` span open at the time (``unspanned``
  where none is).

    python bench/program_trace.py <file.xplane.pb>    # prints a summary
"""

from __future__ import annotations

import dataclasses
import re
import sys

import numpy as np

from trace_reduce import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _gaps, load
from trace_reduce import short_name

MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("quiver.", "bench.")
UNSPANNED = "unspanned"
_RUN_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_program(8487115158256911563)`` -> ``jit_program``."""
    return _RUN_ID.sub("", event_name)


@dataclasses.dataclass
class DeviceTimeline:
    names: list[str]          # leaf op names
    module: list[str]         # the module run each leaf op falls in
    start: np.ndarray         # float64 ns, clipped to the window
    end: np.ndarray
    containers: list[tuple[str, str, float]]   # (name, module, seconds)
    ops_start: np.ndarray     # every op event (leaf or not), for idle
    ops_end: np.ndarray


@dataclasses.dataclass
class ProgramTrace:
    window: tuple[float, float]                   # ns
    devices: dict[int, DeviceTimeline]
    spans: list[tuple[str, float, float]]         # quiver.* / bench.*

    def span_count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)

    def scope_seconds(self, module: str,
                      scopes: dict[str, str]) -> dict[str, float]:
        """Leaf-op device seconds of ``module`` by scope (all devices
        summed); leaf ops the map does not name count as
        ``unmapped``."""
        out: dict[str, float] = {}
        for d in self.devices.values():
            for name, mod, s, e in zip(d.names, d.module, d.start, d.end):
                if mod == module:
                    key = scopes.get(name, "unmapped")
                    out[key] = out.get(key, 0.0) + (e - s) / 1e9
        return out

    def unmapped_ops(self, module: str,
                     scopes: dict[str, str]) -> dict[str, float]:
        """Leaf-op seconds of ``module`` per op name the map misses."""
        out: dict[str, float] = {}
        for d in self.devices.values():
            for name, mod, s, e in zip(d.names, d.module, d.start, d.end):
                if mod == module and name not in scopes:
                    out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def idle_by_innermost(self) -> dict[str, float]:
        """Idle device seconds inside the window (averaged over the
        devices) by the innermost host span open at the time:
        ``quiver.window`` holds what no stage inside it does, and
        ``unspanned`` what no span does.  The parts add up to the
        window's idle time."""
        if not self.devices:
            return {}
        segments = innermost_segments(self.spans, *self.window)
        totals: dict[str, float] = {}
        for d in self.devices.values():
            idle_before = _idle_before(d.ops_start, d.ops_end, *self.window)
            for name, s, e in segments:
                got = float(idle_before(e) - idle_before(s)) / 1e9
                totals[name] = totals.get(name, 0.0) + got
        k = len(self.devices)
        return {n: v / k for n, v in totals.items()}


def envelope_idle(pt: ProgramTrace, envelope: str = "quiver.window"):
    """Idle seconds with ``envelope`` innermost, by the spans on either
    side (``"finalize>scatter"``): what lies between the stages."""
    segments = innermost_segments(pt.spans, *pt.window)
    out: dict[str, float] = {}
    for d in pt.devices.values():
        idle_before = _idle_before(d.ops_start, d.ops_end, *pt.window)
        for i, (name, s, e) in enumerate(segments):
            if name != envelope:
                continue
            prev = segments[i - 1][0] if i else UNSPANNED
            after = segments[i + 1][0] if i + 1 < len(segments) else UNSPANNED
            key = f"{prev}>{after}"
            got = float(idle_before(e) - idle_before(s)) / 1e9
            out[key] = out.get(key, 0.0) + got / len(pt.devices)
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def innermost_segments(spans, w0: float, w1: float):
    """Cut [w0, w1] into (name, start, end) pieces, each named by the
    innermost span open over it: the one opened last among those open
    (spans of one thread nest), or ``unspanned``."""
    bounds = sorted({w0, w1, *(t for _, s, e in spans for t in (s, e)
                               if w0 < t < w1)})
    starts = sorted(spans, key=lambda x: (x[1], -x[2]))
    out = []
    open_: list[tuple[str, float, float]] = []
    i = 0
    for a, b in zip(bounds[:-1], bounds[1:]):
        while i < len(starts) and starts[i][1] <= a:
            open_.append(starts[i])
            i += 1
        open_ = [x for x in open_ if x[2] > a]
        name = open_[-1][0] if open_ else UNSPANNED
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def _idle_before(start, end, w0, w1):
    """t -> idle ns in [w0, t] of the ops' intervals."""
    gs, ge = _gaps(start, end, w0, w1)
    cum = np.concatenate([[0.0], np.cumsum(ge - gs)])

    def before(t):
        i = int(np.searchsorted(gs, t, side="right")) - 1
        if i < 0:
            return 0.0
        return cum[i] + min(max(t - gs[i], 0.0), ge[i] - gs[i])

    return before


def leaf_mask(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """True for the events that enclose no other event: ordered by
    start (longest first on a tie), an event is a container when the
    next one starts before it ends."""
    order = np.lexsort((-end, start))
    s, e = start[order], end[order]
    container = np.zeros(len(s), dtype=bool)
    if len(s) > 1:
        container[:-1] = s[1:] < e[:-1]
    leaf = np.empty(len(s), dtype=bool)
    leaf[order] = ~container
    return leaf


def reduce(source) -> ProgramTrace:
    pd = load(source)
    raw: dict[int, tuple] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(short_name(ev.name), ev.start_ns, ev.end_ns)
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(module_name(ev.name), ev.start_ns, ev.end_ns)
                            for ev in line.events]
            raw[int(m.group(1))] = (ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.end_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    w0, w1 = windows[0]
    devices = {d: _timeline(ops, mods, w0, w1)
               for d, (ops, mods) in raw.items()}
    spans = sorted((x for x in spans
                    if x[0] != WINDOW_SPAN and x[2] > w0 and x[1] < w1),
                   key=lambda x: x[1])
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in spans]
    return ProgramTrace((w0, w1), devices, spans)


def _timeline(ops, mods, w0, w1) -> DeviceTimeline:
    names = [n for n, _, _ in ops]
    start = np.asarray([s for _, s, _ in ops], np.float64)
    end = np.asarray([e for _, _, e in ops], np.float64)
    leaf = leaf_mask(start, end)
    mods = sorted(mods, key=lambda x: x[1])
    m_start = np.asarray([s for _, s, _ in mods], np.float64)
    m_end = np.asarray([e for _, _, e in mods], np.float64)
    at = np.searchsorted(m_start, start, side="right") - 1
    inside_mod = ((at >= 0) & (start < m_end[np.maximum(at, 0)])
                  if len(mods) else np.zeros(len(ops), bool))
    module = [mods[i][0] if ok else "" for i, ok in zip(at, inside_mod)]
    inside = (end > w0) & (start < w1)
    cs, ce = np.clip(start, w0, w1), np.clip(end, w0, w1)
    keep = inside & leaf
    containers = [(names[i], module[i], (ce[i] - cs[i]) / 1e9)
                  for i in np.flatnonzero(inside & ~leaf)]
    return DeviceTimeline(
        [names[i] for i in np.flatnonzero(keep)],
        [module[i] for i in np.flatnonzero(keep)],
        cs[keep], ce[keep], containers, cs[inside], ce[inside])


# -- the numbers the program's names give -----------------------------

# innermost spans whose idle is the engine's launch side and its
# finalize side of a pump (``quiver.window`` is the envelope, no stage)
LAUNCH_STAGES = ("quiver.coalesce", "quiver.launch", "quiver.encode",
                 "quiver.dispatch")
FINALIZE_STAGES = ("quiver.finalize", "quiver.scatter")
ENVELOPE = "quiver.window"


def numbers(pt: ProgramTrace, scopes: dict | None, loop: dict,
            queries: int) -> dict:
    """Per-layer numbers of one traced window from the program's own
    names: ``scopes`` is ``{"module", "ops"}`` from
    ``PlanCache.op_scopes`` (None: the device split is left out),
    ``loop`` the deltas of the plan cache's loop counters over the
    window, ``queries`` the query rows answered in it.  A number whose
    inputs are missing is None."""
    def per(x, n, scale=1.0):
        return x / n * scale if n else None

    out: dict = {}
    pumps = pt.span_count(ENVELOPE)
    idle = pt.idle_by_innermost()
    stage_idle = sum(v for k, v in idle.items()
                     if k.startswith("quiver.") and k != ENVELOPE)
    under_pump = idle.get("bench.pump", 0.0) + sum(
        v for k, v in idle.items() if k.startswith("quiver."))
    out["engine.launch_idle_ms_per_pump"] = per(
        sum(idle.get(k, 0.0) for k in LAUNCH_STAGES), pumps, 1e3)
    out["engine.finalize_idle_ms_per_pump"] = per(
        sum(idle.get(k, 0.0) for k in FINALIZE_STAGES), pumps, 1e3)
    out["stage_idle_share_of_pump_idle"] = per(stage_idle, under_pump)
    out["idle_s"] = sum(idle.values())
    out["window_idle_between"] = envelope_idle(pt, ENVELOPE)
    out["pumps"] = pumps
    iters = loop.get("loop_iterations")
    out["beam.iterations_per_batch"] = per(iters, loop.get("nav_chunks"))
    hops = loop.get("nav_hops")
    out["beam.lockstep_share"] = per(
        hops, hops + loop["wait_iterations"] if hops else None, 100.0)
    if scopes is None:
        return out
    module, ops = scopes["module"], scopes["ops"]
    by = pt.scope_seconds(module, ops)
    leaf = sum(by.values())
    loop_s = sum(secs for d in pt.devices.values()
                 for name, mod, secs in d.containers
                 if mod == module and ops.get(name) == "quiver.beam")
    beam_parts = sum(v for k, v in by.items()
                     if k.startswith("quiver.beam."))
    out["beam.us_per_iteration"] = per(loop_s, iters, 1e6)
    for name, parts in (
            ("beam.sort_us_per_query", ("quiver.beam.pick",
                                        "quiver.beam.merge")),
            ("beam.dedup_us_per_query", ("quiver.beam.dedup",)),
            ("beam.visited_us_per_query", ("quiver.beam.visited",)),
            ("beam.distance_us_per_query", ("quiver.beam.distance",)),
            ("rerank.device_us_per_query", ("quiver.rerank",))):
        out[name] = per(sum(by.get(k, 0.0) for k in parts), queries, 1e6)
    out["mapped_share_of_leaf_s"] = per(leaf - by.get("unmapped", 0.0),
                                        leaf)
    out["beam_parts_share_of_loop_s"] = per(beam_parts, loop_s)
    out["scope_s"] = dict(sorted(by.items(), key=lambda x: -x[1]))
    return out


def summarize(source) -> None:
    """Print the spans, modules and idle attribution of a trace."""
    pt = reduce(source)
    print(f"window {(pt.window[1] - pt.window[0]) / 1e9:.6f} s")
    counts: dict[str, int] = {}
    for n, _, _ in pt.spans:
        counts[n] = counts.get(n, 0) + 1
    print(f"spans {sorted(counts.items())}")
    for d, tl in pt.devices.items():
        mods = sorted(set(tl.module))
        print(f"device {d}: {len(tl.names)} leaf ops in modules {mods}; "
              f"containers {sorted({n for n, _, _ in tl.containers})}")
    idle = sorted(pt.idle_by_innermost().items(), key=lambda x: -x[1])
    print(f"idle by innermost span {idle}")


if __name__ == "__main__":
    summarize(sys.argv[1])
