"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [table5 table7 ...]

Prints ``name,us_per_call,derived`` CSV rows (the harness contract),
writes JSON artifacts to experiments/bench/, and records each suite as
a machine-readable ``BENCH_<name>.json`` at the repo root (the perf
trajectory: recall/QPS/memory per config, one artifact per suite).
Scale via REPRO_BENCH_N (default 10k vectors; the paper uses 1M —
constants scale, orderings don't).
"""

from __future__ import annotations

import sys
import time

from benchmarks import (
    ablation_adc,
    ablation_bits,
    construction,
    filtered,
    graphhealth,
    kernel_bench,
    multitenant,
    quality,
    serve,
    streaming,
    table2_memory,
    table5_recall_qps,
    table6_baselines,
    table7_boundary,
)
from benchmarks.common import emit, write_bench_json
from repro.compile_cache import enable_compile_cache

TABLES = {
    "kernel_bench": kernel_bench.run,
    "table2": table2_memory.run,
    "table5": table5_recall_qps.run,
    "table6": table6_baselines.run,
    "table7": table7_boundary.run,
    "boundary": table7_boundary.run_boundary,
    "ablation_adc": ablation_adc.run,
    "ablation_bits": ablation_bits.run,
    "construction": construction.run,
    "streaming": streaming.run,
    "filtered": filtered.run,
    "serve": serve.run,
    "multitenant": multitenant.run,
    "quality": quality.run,
    "graphhealth": graphhealth.run,
}


def main() -> None:
    enable_compile_cache()
    names = sys.argv[1:] or list(TABLES)
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.perf_counter()
        rows = TABLES[name]()
        # a suite may return (rows, extra) to stamp suite-level fields
        # (e.g. the multitenant tenant/drift reports) into its artifact
        extra = None
        if isinstance(rows, tuple):
            rows, extra = rows
        emit(rows, name)
        path = write_bench_json(rows, name, extra)
        print(f"# {name} done in {time.perf_counter()-t0:.0f}s "
              f"-> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
