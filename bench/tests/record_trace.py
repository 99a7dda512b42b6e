#!/usr/bin/env python3
"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py [--out bench/tests/data/v5e_batch_small.xplane.pb.gz]

Needs a TPU.  Builds a 4,096-vector index of the 768-d configuration,
serves the closed batch mix for a few windows under the profiler with
the harness's own spans, writes the trace (gzipped) and prints its
summary: planes, lines, event counts and a few events with stats.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
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
        BENCH / "tests" / "data" / "v5e_batch_small.xplane.pb.gz"))
    ap.add_argument("--seconds", type=float, default=0.05)
    args = ap.parse_args()
    from run import chips, configure_jax

    jax = configure_jax(False)
    chips(jax, 1, False)
    from harness import Spans, closed_loop, load_cell, set_up, start_trace
    from trace_reduce import summarize

    cell = load_cell("cohere768.batch-closed")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, n=4096, pool=256))
    s = set_up(cell, traced=True, rehearse=False)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        start_trace(tmp)
        w = closed_loop(s.engine, s.pool, cell.mix, args.seconds,
                        np.random.default_rng(7), Spans(True))
        jax.profiler.stop_trace()
        found = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1]
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        with open(found, "rb") as f, gzip.open(out, "wb") as g:
            g.write(f.read())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {w.pumps} windows, {w.queries} queries -> {out} "
          f"({out.stat().st_size} bytes)")
    summarize(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
