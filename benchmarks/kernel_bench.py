"""Hot-kernel microbenchmarks.

Rates are measured on whatever device jax runs on, and every row names
that device.  Roofline columns — the HBM-bound rate of the device the
run was on, from ``repro.tools.roofline.PEAKS`` — are written only on a
TPU: a CPU or interpret-mode run times XLA's CPU backend, and a chip's
peak next to it would read as a chip number.

    symmetric BQ distance streams (2W words x 4 B) per base row
      -> pairs/s at HBM roofline = hbm_bw / (D/4 B)
    vs float32 dot: 4D B per row -> 16x fewer pairs/s at the same
    bandwidth — the TPU restatement of the paper's "20x cheaper per
    hop" claim (theirs is compute-bound AVX-512; ours is bandwidth-
    bound VPU, and the 16x is exactly the compression ratio).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq
from repro.core.baselines import recall_at_k
from repro.core.beam import batched_beam_search
from repro.tools.roofline import peaks_for

from benchmarks.common import dataset, emit, ground_truth, index_for


def _device() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def beam_width_sweep(ef: int = 64, k: int = 10) -> list[dict]:
    """Multi-expansion beam search: recall at equal distance-eval budget.

    The beam expansion width L turns the per-hop distance batch from
    (R,) into (L*R,) — the shape a Pallas/VPU kernel wants.  Budget is
    held constant across L by capping hops at ceil(H1 / L), where H1 is
    the greedy (L=1) run's natural mean hop count, so every row spends
    ~H1*R distance evaluations per query.
    """
    idx, _ = index_for("minilm-surrogate")
    _, queries = dataset("minilm-surrogate")
    gt = ground_truth("minilm-surrogate", k=k)
    backend = idx.backend()
    q = jnp.asarray(queries, jnp.float32)
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    reprs = backend.encode_queries(q)
    n = idx.sigs.words.shape[0]
    r = idx.adjacency.shape[1]

    def rerank(res):
        from repro.core.index import _rerank_f32
        ids, _ = _rerank_f32(res.ids, q, idx.vectors, k)
        return np.asarray(ids)

    # greedy reference: its natural fresh-evaluation count defines the
    # shared budget; every L (including 1) then runs under the same
    # max_evals cap, so no width gets free extra distance evaluations.
    # (Fresh evals — not hop slots — are the hardware-honest budget:
    # each fresh eval is one popcount row regardless of batch shape.)
    res1 = batched_beam_search(
        reprs, idx.adjacency, jnp.int32(idx.medoid),
        dist_fn=backend.dist_fn, ef=ef, n=n, expand=1,
    )
    budget = int(round(float(np.asarray(res1.evals).mean())))

    rows = []
    for expand in (1, 2, 4):
        run = jax.jit(lambda rep: batched_beam_search(
            rep, idx.adjacency, jnp.int32(idx.medoid),
            dist_fn=backend.dist_fn, ef=ef, n=n, expand=expand,
            max_evals=budget,
        ))
        res = run(reprs)
        jax.block_until_ready(res)
        t0 = time.perf_counter()
        res = run(reprs)
        jax.block_until_ready(res)
        dt = time.perf_counter() - t0
        rows.append({
            "name": f"kernel/beam_expand_L{expand}",
            "us_per_call": round(dt * 1e6 / len(queries), 1),
            "recall_at_10": round(recall_at_k(rerank(res), gt), 4),
            "mean_hops": round(float(np.asarray(res.hops).mean()), 1),
            "dist_evals_per_query": round(
                float(np.asarray(res.evals).mean()), 1),
            "eval_budget": budget,
            "dist_batch_width": expand * r,
        })
    return rows


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    device = _device()
    # roofline columns only where the peaks belong to the device timed
    hbm_bw = (peaks_for(device["device_kind"])["hbm_bw"]
              if device["platform"] == "tpu" else None)
    for dim in (384, 768, 1536):
        n = 100_000
        base = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
        sigs = bq.encode(base)
        q = bq.encode(base[:8])

        fn = jax.jit(lambda a, b: bq.pairwise_distance(
            bq.Signature(a, dim), bq.Signature(b, dim)))
        fn(q.words, sigs.words).block_until_ready()
        t0 = time.perf_counter()
        fn(q.words, sigs.words).block_until_ready()
        dt = time.perf_counter() - t0
        pairs = 8 * n
        bytes_per_row = sigs.words.shape[-1] * 4
        row = {
            "name": f"kernel/bq_distance_d{dim}",
            "us_per_call": round(dt * 1e6, 1),
            "mpairs_per_s": round(pairs / dt / 1e6, 1),
            **device,
        }
        if hbm_bw is not None:
            row["roofline_mpairs_per_s"] = round(
                hbm_bw / bytes_per_row / 1e6, 1)
            row["f32_roofline_mpairs_per_s"] = round(
                hbm_bw / (4 * dim) / 1e6, 1)
        rows.append(row)

        # binarize throughput (stage-0 bulk pre-installation)
        x32 = base[:20_000]
        enc = jax.jit(lambda v: bq.encode(v).words)
        enc(x32).block_until_ready()
        t0 = time.perf_counter()
        enc(x32).block_until_ready()
        dt = time.perf_counter() - t0
        row = {
            "name": f"kernel/binarize_d{dim}",
            "us_per_call": round(dt * 1e6 / len(x32), 2),
            "mvecs_per_s": round(len(x32) / dt / 1e6, 2),
            **device,
        }
        if hbm_bw is not None:
            row["roofline_mvecs_per_s"] = round(
                hbm_bw / (4 * dim) / 1e6, 1)
        rows.append(row)
    rows.extend(beam_width_sweep())
    return rows


if __name__ == "__main__":
    emit(run(), "kernel_bench")
