#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, computed one precision below the
one the configuration states, must come out as not correct.

    python bench/control.py --config cohere-768-32k

The configuration's corpus and query pool at its size, the exact
float64 top-k (the reference), and then brute-force top-k answers
computed on the device at each precision: ``float32``
(``Precision.HIGHEST``, the stated one), ``high`` (``Precision.HIGH``:
three bfloat16 passes on a TPU, float32 on the CPU) and ``bf16`` (one
bfloat16 pass with float32 accumulation, the TPU's default, spelt out
so that the CPU computes the same control).  Every answer set goes
through the same ``reference.compare`` as a run's, and one JSON line
per precision gives the compared numbers.  The pool, under 4,096
queries, is compared whole, so no seed enters.  ``readings`` takes a
smaller ``n`` and ``pool`` for a test.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
PRECISIONS = ("float32", "high", "bf16")


def cosines(q, b, precision: str):
    """(Q, N) inner products of float32 rows at ``precision``."""
    import jax
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.matmul(q, b.T, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return jnp.matmul(q, b.T, precision=jax.lax.Precision.HIGH)
    if precision == "bf16":
        return jnp.matmul(q.astype(jnp.bfloat16), b.astype(jnp.bfloat16).T,
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def brute_force(base, pool, k: int, precision: str, block: int = 256):
    """(ids, scores) of the top-k cosines on the default device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def topk(q, b):
        return jax.lax.top_k(cosines(q, b, precision), k)

    b = jnp.asarray(base)
    ids, scores = [], []
    for s in range(0, len(pool), block):
        sc, ix = topk(jnp.asarray(pool[s:s + block]), b)
        ids.append(np.asarray(ix))
        scores.append(np.asarray(sc))
    return np.concatenate(ids), np.concatenate(scores)


def readings(config: dict, precisions, *, n=None, pool=None):
    from corpus import make_corpus
    from reference import compare, exact_topk

    n = n or config["n"]
    pool_n = pool or config["pool"]
    k = config["k"]
    base, queries = make_corpus(config["corpus"], n, pool_n,
                                config["corpus_seed"])
    truth, _ = exact_topk(base, queries, k)
    out = []
    for p in precisions:
        ids, scores = brute_force(base, queries, k, p)
        checks = compare(
            served_ids=ids, served_scores=scores,
            pool_rows=np.arange(len(queries)), missing=0, base=base,
            pool=queries, truth_ids=truth,
            recall_floor=config["recall_at_10_min"],
            rng=np.random.default_rng(0))
        out.append({"precision": p,
                    "correct": checks.pop("correct"),
                    **{name: c["value"] for name, c in checks.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    import jax

    config = json.loads(
        (BENCH / "configs" / f"{args.config}.json").read_text())
    print(f"control: {args.config} on {jax.devices()[0].device_kind}",
          file=sys.stderr, flush=True)
    for row in readings(config, PRECISIONS):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
