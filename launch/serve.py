#!/usr/bin/env python3
"""Runtime-tuned launcher for the serve benchmark / QueryEngine.

    python launch/serve.py [benchmarks.run args...]   # default: serve

The allocator preload must be in place *before* the process that
imports jax starts — LD_PRELOAD is read by the dynamic linker — so this
script sets up the environment and ``exec``s a fresh interpreter running
``benchmarks.run`` rather than importing anything heavy itself.  Jax
then picks its device as usual: the TPU where one is attached, the CPU
otherwise; nothing here pins or hides the platform, and runtime errors
(libtpu's included) reach the log.

What it applies:

* **tcmalloc preload** — glibc malloc fragments badly under the serve
  engine's steady stream of short-lived numpy result buffers; tcmalloc's
  thread caches keep host-side staging allocations cheap.  Preloaded
  when a system copy exists, otherwise launch proceeds with a pointer to
  the package that provides it (we never install anything ourselves).
* ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` — silence tcmalloc's
  large-alloc warnings for corpus-sized arrays.
* ``JAX_ENABLE_X64=0`` — keep everything in 32-bit; the hot path is
  2-bit signatures and float32 rerank, fp64 would double rerank traffic.
"""

from __future__ import annotations

import os
import pathlib
import sys

TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc_minimal.so.4",
)


def tuned_env() -> dict:
    env = dict(os.environ)

    tcmalloc = next(
        (p for p in TCMALLOC_CANDIDATES if pathlib.Path(p).exists()), None
    )
    if tcmalloc:
        preload = env.get("LD_PRELOAD", "")
        if tcmalloc not in preload:
            env["LD_PRELOAD"] = f"{preload}:{tcmalloc}".strip(":")
        env.setdefault(
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000"
        )
        print(f"[launch/serve] tcmalloc: {tcmalloc}", file=sys.stderr)
    else:
        print(
            "[launch/serve] tcmalloc not found; running with glibc "
            "malloc (install libgoogle-perftools4 / gperftools for the "
            "preload path)",
            file=sys.stderr,
        )

    env.setdefault("JAX_ENABLE_X64", "0")

    # observability defaults (DESIGN.md §12): the serve/multitenant
    # benchmarks build an ObsHub from these — periodic reports land as
    # JSONL under experiments/obs/, and REPRO_METRICS_PORT (opt-in, no
    # default: it opens a listening socket) serves the same registry as
    # a Prometheus text endpoint at /metrics.
    repo = pathlib.Path(__file__).resolve().parents[1]
    env.setdefault(
        "REPRO_OBS_JSONL", str(repo / "experiments" / "obs" / "serve.jsonl")
    )
    env.setdefault("REPRO_OBS_INTERVAL_S", "5")

    src = str(repo / "src")
    pypath = env.get("PYTHONPATH", "")
    if src not in pypath.split(os.pathsep):
        env["PYTHONPATH"] = os.pathsep.join(p for p in (src, pypath) if p)
    return env


def main() -> None:
    env = tuned_env()
    tables = sys.argv[1:] or ["serve"]
    argv = [sys.executable, "-m", "benchmarks.run", *tables]
    print(f"[launch/serve] exec: {' '.join(argv)}", file=sys.stderr)
    repo = pathlib.Path(__file__).resolve().parents[1]
    os.chdir(repo)
    os.execve(sys.executable, argv, env)


if __name__ == "__main__":
    main()
