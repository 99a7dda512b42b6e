#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --workload <cell> --seed <n> --seconds 2 --rehearse

Set-up (``setup_s``, from process start to the first due request):
the configuration's corpus and a query pool from ``--seed``,
``QuIVerIndex.build``, ``QueryEngine`` and a warm-up of the one
coalesced size the cell's closed-loop traffic makes.  Then the traffic
mix runs for ``--seconds`` against ``QueryEngine.submit``/``pump``; the
served answers are compared with an exact float64 reference once the
window has closed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; ``checks`` (each compared number and its limit) comes
last, and the same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse`` runs the same path on the CPU at
a tiny size with the Pallas kernels interpreted; it prints counts and
checks and no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, interpreted kernels; no metric")
    return ap.parse_args(argv)


def configure_jax(rehearse: bool):
    """Persistent compile cache at the benchmark's fixed path in the
    checkout, every program cached and none evicted (a size limit, which
    the environment may set, turns on eviction: every write then scans
    the whole directory, and entries written without it fail to load);
    interpreted kernels to rehearse."""
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("QUIVER_DISPATCH", "pallas")
    import jax

    from harness import CACHE_DIR

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def chips(jax, want: int, rehearse: bool):
    """The devices the cell runs on, or exit 2 without a result."""
    devices = jax.devices()
    if rehearse:
        return devices[:want]
    if devices[0].platform != "tpu":
        print(f"bench: jax found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < want:
        print(f"bench: the cell needs {want} chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:want]


def breakdown(reduced) -> dict:
    ops = sorted(reduced.op_seconds().items(), key=lambda x: -x[1])[:10]
    idle = sorted(reduced.idle_by_span().items(), key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}


def finite(v):
    """A number for strict JSON: a non-finite reading prints as null."""
    return v if not isinstance(v, float) or math.isfinite(v) else None


def check_lines(checks: dict) -> list[str]:
    return [f"check {name}: {c['value']!r} {c['holds']} {c['limit']!r}"
            for name, c in checks.items() if name != "correct"]


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device_peaks, load_cell, log, reader, run

    cell = load_cell(args.workload)
    jax = configure_jax(args.rehearse)
    devices = chips(jax, cell.chips, args.rehearse)
    dev = devices[0]
    log(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev.platform}:{dev.device_kind} "
        f"x{len(devices)}")
    peaks = None if args.rehearse else device_peaks(dev.device_kind)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        rec = run(cell, seed=args.seed, seconds=args.seconds,
                  traced=bool(args.trace), rehearse=args.rehearse,
                  t_start=T_START, devices=devices, trace_dir=trace_dir)
        reduced = None
        if args.trace:
            from trace_reduce import reduce

            t = time.perf_counter()
            found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
            reduced = reduce(found[-1])
            log(f"trace: reduced {found[-1].stat().st_size} bytes in "
                f"{time.perf_counter() - t:.2f} s; window "
                f"{reduced.window_s:.3f} s, busy {reduced.busy_s():.3f} s")
            top = sorted(reduced.op_seconds().items(), key=lambda x: -x[1])
            log(f"trace: top device ops {top[:25]}")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec["trace"] = reduced
    rec["peaks"] = peaks

    checks = rec.pop("checks")
    w = rec["window"]
    out = {"correct": checks["correct"], "attempted": w.requests,
           "failed": rec["failed"]}
    if args.rehearse:
        out["rehearsal"] = True
        out["counts"] = {"requests": w.requests, "answered": w.answered,
                         "queries": w.queries, "pumps": w.pumps,
                         "compiles_in_window": rec["compiles_in_window"]}
    else:
        wanted = cell.per_layer if args.trace else cell.end_to_end
        metrics = {}
        for m in wanted:
            value = reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": rec["memory_peak_bytes"]}
        if reduced is not None:
            out["device"]["busy_s"] = reduced.busy_s()
            out["device"]["window_s"] = reduced.window_s
            out["breakdown"] = breakdown(reduced)
    out["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.items() if k != "correct"}
    for line in check_lines(checks):
        log(line)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
