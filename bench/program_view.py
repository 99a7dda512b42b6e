#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does, and add what the program
records about itself.

    python bench/program_view.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The arguments, set-up, window, comparison and result line are
``run.py``'s own; on standard error, after its lines:

* ``stages:`` every run: the engine's stage split of the window's
  slowest admission window, and each stage's total over the window
  (``Tracer.stage_report``; stages nest, so ``encode`` and
  ``dispatch`` are part of ``launch``);
* with ``--trace 1``, the second reduction of the same trace by the
  program's names (``program_trace.py``): device seconds of the plan
  program's leaf ops by ``quiver.*`` scope (``PlanCache.op_scopes``),
  the ops no scope names, idle by innermost span, how long the
  reduction took, and one ``program: {json}`` line of the numbers they
  give (``program_trace.numbers``).

A program without the stage tracer, the loop counters or
``op_scopes`` prints ``not recorded`` where they would be.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent

# the plan cache's always-on loop counters (``PlanCache.report()``)
LOOP_COUNTERS = ("nav_chunks", "nav_rows", "nav_hops", "nav_evals",
                 "loop_iterations", "wait_iterations")


def loop_counters(index) -> dict:
    rep = index.plans.report()
    return {k: rep[k] for k in LOOP_COUNTERS if k in rep}


def stage_line(report: dict | None) -> str:
    if report is None:
        return "stages: not recorded by this program"
    totals = " ".join(f"{n}={v['total_s']:.4f}s/{v['count']}"
                      for n, v in report["stages"].items())
    slow = report["slowest_window"]
    if slow is None:
        return f"stages: no window; totals {totals}"
    split = " ".join(f"{n}={v * 1e3:.3f}" for n, v in slow["stages"].items())
    return (f"stages: slowest window {slow['seconds'] * 1e3:.3f}ms@"
            f"{slow['index']} of {report['windows']} [ms] {split}; "
            f"totals {totals}")


def op_scopes(engine, mix: dict) -> dict | None:
    """The plan program's module name and op-to-scope map for the
    engine's default plan at the mix's coalesced size."""
    from repro.core.beam import batch_bucket
    from repro.plan import resolve_plan

    plans = engine.index.plans
    if not hasattr(plans, "op_scopes"):
        return None
    plan, _ = resolve_plan(engine.index, k=engine.default_k,
                           ef=engine.default_ef,
                           query_batch=engine.query_batch)
    rows = int(mix["clients"]) * int(mix["queries_per_request"])
    module, ops = plans.op_scopes(
        plan, batch_bucket(rows, engine.query_batch))
    return {"module": module, "ops": ops}


def main(argv=None) -> int:
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    import program_trace
    import run
    import trace_reduce

    run.T_START = T_START
    seen: dict = {}
    closed_loop, reduce = harness.closed_loop, trace_reduce.reduce

    def viewed_loop(engine, pool, mix, seconds, rng, spans):
        tracer = getattr(engine, "tracer", None)
        if not hasattr(tracer, "stage_report"):
            tracer = None
        if tracer is not None:
            tracer.reset()
        c0 = loop_counters(engine.index)
        w = closed_loop(engine, pool, mix, seconds, rng, spans)
        c1 = loop_counters(engine.index)
        seen["loop"] = {k: c1[k] - c0[k] for k in c1}
        seen["queries"] = w.queries
        harness.log(stage_line(tracer.stage_report() if tracer else None))
        if spans.traced:
            # the op map compiles the program again: after the run's
            # compile count, with the trace
            seen["engine"] = (engine, mix)
        return w

    def viewed_reduce(source):
        out = reduce(source)
        t = time.perf_counter()
        seen["scopes"] = op_scopes(*seen.pop("engine"))
        harness.log(f"op scopes: in {time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        seen["program"] = program_trace.reduce(source)
        harness.log(f"trace: second reduction (program names) in "
                    f"{time.perf_counter() - t:.2f} s")
        return out

    harness.closed_loop, trace_reduce.reduce = viewed_loop, viewed_reduce
    try:
        rc = run.main(argv)
    finally:
        harness.closed_loop, trace_reduce.reduce = closed_loop, reduce
    pt = seen.get("program")
    if pt is not None:
        report(pt, seen.get("scopes"), seen["loop"], seen["queries"])
    return rc


def report(pt, scopes, loop, queries) -> None:
    from harness import log
    from program_trace import numbers

    idle = sorted(pt.idle_by_innermost().items(), key=lambda x: -x[1])
    log(f"trace: idle by innermost span {idle}")
    if scopes is None:
        log("trace: op scopes not recorded by this program")
    else:
        miss = pt.unmapped_ops(scopes["module"], scopes["ops"])
        log(f"trace: {scopes['module']} unmapped leaf ops "
            f"{sorted(miss.items(), key=lambda x: -x[1])[:20]}")
    log("program: " + json.dumps(
        {"loop": loop, **numbers(pt, scopes, loop, queries)}))


if __name__ == "__main__":
    sys.exit(main())
