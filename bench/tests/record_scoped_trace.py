#!/usr/bin/env python3
"""Record the scoped device trace that ``test_program_trace.py`` reads.

    python bench/tests/record_scoped_trace.py [--out bench/tests/data/v5e_batch_scoped]

Needs a TPU.  ``record_trace.py``'s recipe (a 4,096-vector index of the
768-d configuration serving the closed batch mix under the profiler,
with the harness's own spans) for a few windows, on a program whose
stages and device ops carry their ``quiver.*`` names; writes the trace
(``<out>.xplane.pb.gz``) and the plan program's op-to-scope map from
``PlanCache.op_scopes`` (``<out>.ops.json``), and prints the trace's
summary by the program's names.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import pathlib
import shutil
import sys
import tempfile

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(
        BENCH / "tests" / "data" / "v5e_batch_scoped"))
    ap.add_argument("--seconds", type=float, default=0.25)
    args = ap.parse_args()
    from run import chips, configure_jax

    jax = configure_jax(False)
    chips(jax, 1, False)
    from harness import Spans, closed_loop, load_cell, set_up, start_trace
    from program_trace import summarize
    from program_view import op_scopes

    cell = load_cell("cohere768.batch-closed")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, n=4096, pool=256))
    s = set_up(cell, traced=True, rehearse=False)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    trace = out.with_name(out.name + ".xplane.pb.gz")
    try:
        start_trace(tmp)
        w = closed_loop(s.engine, s.pool, cell.mix, args.seconds,
                        np.random.default_rng(7), Spans(True))
        jax.profiler.stop_trace()
        found = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1]
        with open(found, "rb") as f, gzip.open(trace, "wb") as g:
            g.write(f.read())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    scopes = op_scopes(s.engine, cell.mix)
    ops = out.with_name(out.name + ".ops.json")
    ops.write_text(json.dumps(scopes, indent=0, sort_keys=True) + "\n")
    print(f"recorded {w.pumps} windows, {w.queries} queries -> {trace} "
          f"({trace.stat().st_size} bytes), {len(scopes['ops'])} ops of "
          f"{scopes['module']} -> {ops}")
    summarize(trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
