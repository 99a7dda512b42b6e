#!/usr/bin/env python3
"""Faults planted under the timed path, to show that the comparison
which decides ``correct`` fails each of them.

    python bench/faults.py --workload cohere768.batch-closed --seeds 1,2,3

Each fault wraps ``PlanCache.finalize``, where the plan program's
answers come back to ``QueryEngine.pump``:

* ``altered``: each answer's first id moved to a neighbouring corpus
  row, as if it were altered where it is produced;
* ``half_batch``: the second half of each window's rows left out and
  given the first half's answers;
* ``stale``: each window returns the previous window's answers of the
  same shape, as a step that returns its state unchanged.

For each seed and fault, one whole run of the cell (``run.main``, on
the chips of this machine, or with ``--rehearse`` on the CPU) with the
fault planted; one JSON line per run gives ``correct`` and each
compared number.  The cell's own runs never plant one.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


def altered(ids, scores, last):
    first = ids[:, 0]
    ids[:, 0] = np.where(first > 0, first - 1, first + 1)
    return ids, scores


def half_batch(ids, scores, last):
    half = (len(ids) + 1) // 2
    rest = len(ids) - half
    ids[half:] = ids[:rest]
    scores[half:] = scores[:rest]
    return ids, scores


def stale(ids, scores, last):
    prev = last.get(ids.shape)
    last[ids.shape] = (ids.copy(), scores.copy())
    return prev if prev is not None else (ids, scores)


FAULTS = {"altered": altered, "half_batch": half_batch, "stale": stale}


@contextlib.contextmanager
def planted(name: str):
    """``PlanCache.finalize`` with fault ``name`` applied to what it
    returns, for the duration of the block."""
    from repro.plan.cache import PlanCache

    fault = FAULTS[name]
    finalize = PlanCache.finalize
    last: dict = {}

    def wrapped(self, pending):
        ids, scores = finalize(self, pending)
        return fault(np.array(ids), np.array(scores), last)

    PlanCache.finalize = wrapped
    try:
        yield
    finally:
        PlanCache.finalize = finalize


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run

    for seed in (int(s) for s in args.seeds.split(",")):
        for name in args.faults.split(","):
            out = io.StringIO()
            argv_run = ["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds)]
            with planted(name), contextlib.redirect_stdout(out):
                run.main(argv_run + (["--rehearse"] if args.rehearse
                                     else []))
            line = json.loads(out.getvalue().strip().splitlines()[-1])
            print(json.dumps({
                "seed": seed, "fault": name, "correct": line["correct"],
                **{k: c["value"] for k, c in line["checks"].items()}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
