import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the
device count at first init); everything below is ordinary code.

For each cell this driver:
  1. builds the model bundle and ShapeDtypeStruct input specs,
  2. jits the train/prefill/decode step with production shardings,
  3. ``.lower().compile()`` on the 16x16 (and optionally 2x16x16) mesh,
  4. prints ``memory_analysis()`` + ``cost_analysis()`` and writes the
     roofline terms to ``experiments/dryrun/<cell>.json``.

Usage:
    python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --mesh both
"""

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.base import (
    SHAPES, all_configs, cell_is_supported, get_config,
)
from repro.dist.cache_sharding import batch_shardings, cache_shardings
from repro.dist.sharding import (
    param_shardings, serve_param_shardings, use_mesh,
)
from repro.launch.mesh import dp_size, make_production_mesh
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.tools.jaxpr_cost import trace_cost
from repro.tools.roofline import analyze, model_flops_for
from repro.train.train_step import (
    TrainConfig, make_train_step, suggest_n_micro,
)

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# the chip the production mesh models; its peaks bound the roofline terms
TARGET_KIND = "TPU v5 lite"


def _opt_dtype(cfg):
    # bf16 optimizer state for >=30B models (DESIGN.md §7 memory budget)
    import jax.numpy as jnp
    return jnp.bfloat16 if cfg.param_count() >= 30e9 else jnp.float32


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               n_micro: int | None = None):
    """Lower + compile one cell; returns (compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_supported(cfg, shape)
    if not ok:
        return None, {"skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = build_model(cfg)
    specs = bundle.input_specs(shape)
    t0 = time.perf_counter()

    with mesh, use_mesh(mesh):
        p_shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        p_sh = param_shardings(mesh, p_shapes)
        b_sh = batch_shardings(mesh, specs)

        if shape.kind == "train":
            nm = n_micro or suggest_n_micro(cfg, shape, dp_size(mesh))
            tc = TrainConfig(
                n_micro=nm, adamw=AdamWConfig(state_dtype=_opt_dtype(cfg))
            )
            step_fn = make_train_step(bundle, tc)
            o_shapes = jax.eval_shape(
                lambda p: init_opt_state(p, tc.adamw), p_shapes
            )
            from jax.sharding import NamedSharding, PartitionSpec as P
            o_sh = {
                "mu": p_sh, "nu": p_sh,
                "count": NamedSharding(mesh, P()),
            }
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, b_sh),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_shapes, o_shapes, specs)
            jcost = trace_cost(step_fn, p_shapes, o_shapes, specs)
            mode = f"train_step(n_micro={nm})"
        else:
            # serving layout: TP-only params when they fit (no per-layer
            # FSDP gathers on the decode path)
            p_sh = serve_param_shardings(mesh, p_shapes, cfg.param_count())
            c_shapes = jax.eval_shape(
                lambda: bundle.init_caches(shape.global_batch,
                                           shape.seq_len)
            )
            c_sh = cache_shardings(mesh, c_shapes, shape.global_batch)
            if shape.kind == "prefill":
                jitted = jax.jit(
                    bundle.prefill,
                    in_shardings=(p_sh, b_sh, c_sh),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(p_shapes, specs, c_shapes)
                jcost = trace_cost(bundle.prefill, p_shapes, specs, c_shapes)
                mode = "prefill_step"
            else:
                tok = jax.ShapeDtypeStruct(
                    (shape.global_batch, 1), jax.numpy.int32
                )
                pos = jax.ShapeDtypeStruct((), jax.numpy.int32)
                jitted = jax.jit(
                    bundle.decode,
                    in_shardings=(
                        p_sh, batch_shardings(mesh, {"t": tok})["t"],
                        c_sh, None,
                    ),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(p_shapes, tok, c_shapes, pos)
                jcost = trace_cost(bundle.decode, p_shapes, tok, c_shapes, pos)
                mode = "serve_step(decode)"

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    meta = {
        "jaxpr_costs": jcost,
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode,
        "lower_seconds": round(t_lower, 1),
        "compile_seconds": round(t_compile, 1),
    }
    return compiled, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cell = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    try:
        compiled, meta = lower_cell(arch, shape_name, multi_pod)
    except Exception as e:  # a failure here is a bug in the system
        traceback.print_exc()
        return {"cell": cell, "error": f"{type(e).__name__}: {e}"}

    if compiled is None:
        report = {"cell": cell, **meta}
    else:
        n_chips = 512 if multi_pod else 256
        jcost = meta.pop("jaxpr_costs")
        report = analyze(
            compiled, n_chips=n_chips,
            model_flops=model_flops_for(cfg, shape),
            device_kind=TARGET_KIND,
            jaxpr_costs=jcost,
        )
        report["jaxpr_global"] = {
            "flops": jcost["flops"], "bytes": jcost["bytes"],
        }
        report.update(meta)
        report["cell"] = cell
        print(f"[dryrun] {cell}: memory_analysis="
              f"{report.get('memory_analysis')}")
        print(f"[dryrun] {cell}: flops/dev={report['flops_per_device']:.3e}"
              f" bytes/dev={report['bytes_per_device']:.3e}"
              f" coll_bytes/dev={report['collectives']['total_bytes']:.3e}")
        print(f"[dryrun] {cell}: terms={report['terms_seconds']}"
              f" dominant={report['dominant']}")

    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{cell}.json").write_text(json.dumps(report, indent=2))
    return report


def run_quiver_cell(multi_pod: bool,
                    out_dir: pathlib.Path | None = None) -> dict:
    """Lower + compile the paper's own workload on the production mesh:
    1M x 768 sharded QuIVer fan-out search (256 queries, ef=64, k=10).

    The index is sharded over every mesh axis (4096 vectors/chip at 256
    chips); per-chip hot set = 4096 x (192 B sigs + 288 B adjacency)
    ~ 2 MB — HBM-resident with room to spare (DESIGN.md §7)."""
    import jax.numpy as jnp
    from repro.core import bq
    from repro.core.distributed import make_sharded_search
    from repro.tools.jaxpr_cost import trace_cost

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    cell = f"quiver-1m__search_ef64__{'2x16x16' if multi_pod else '16x16'}"
    dim, ef, k, q = 768, 64, 10, 256
    n_per_shard = 1_048_576 // n_chips
    axes = tuple(mesh.axis_names)
    w2 = 2 * bq.n_words(dim)

    t0 = time.perf_counter()
    fn = make_sharded_search(
        mesh, dim=dim, ef=ef, k=k, n_per_shard=n_per_shard, axis=axes
    )
    sig = jax.ShapeDtypeStruct((n_chips, n_per_shard, w2), jnp.uint32)
    adj = jax.ShapeDtypeStruct((n_chips, n_per_shard, 72), jnp.int32)
    med = jax.ShapeDtypeStruct((n_chips,), jnp.int32)
    vec = jax.ShapeDtypeStruct((n_chips, n_per_shard, dim), jnp.float32)
    liv = jax.ShapeDtypeStruct((n_chips, n_per_shard), jnp.bool_)
    # filter-predicate result mask (all-True when serving unfiltered):
    # same shape as the tombstone mask, one per shard
    rvd = jax.ShapeDtypeStruct((n_chips, n_per_shard), jnp.bool_)
    qw = jax.ShapeDtypeStruct((q, w2), jnp.uint32)
    qf = jax.ShapeDtypeStruct((q, dim), jnp.float32)
    try:
        with mesh:
            lowered = jax.jit(fn).lower(sig, adj, med, vec, liv, rvd,
                                        qw, qf)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            jcost = trace_cost(fn, sig, adj, med, vec, liv, rvd, qw, qf,
                               while_trip_hint=4 * ef + 128)
    except Exception as e:
        traceback.print_exc()
        return {"cell": cell, "error": f"{type(e).__name__}: {e}"}

    report = analyze(
        compiled, n_chips=n_chips,
        # "useful work": Q queries x hops x R neighbour distances x 2D
        # bit-ops-equivalent + rerank GEMV flops
        model_flops=float(q * (4 * ef + 128) * 72 * 2 * dim
                          + q * ef * 2 * dim),
        device_kind=TARGET_KIND,
        jaxpr_costs=jcost,
    )
    report.update({
        "cell": cell, "arch": "quiver-1m", "shape": "search_ef64",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": "sharded_search(while_hint=%d)" % (4 * ef + 128),
        "lower_seconds": round(t_lower, 1),
        "compile_seconds": round(t_compile, 1),
    })
    print(f"[dryrun] {cell}: terms={report['terms_seconds']} "
          f"dominant={report['dominant']}")
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{cell}.json").write_text(json.dumps(report, indent=2))
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    if args.arch == "quiver-1m":
        meshes = {"pod": [False], "multipod": [True],
                  "both": [False, True]}[args.mesh]
        failures = 0
        for mp in meshes:
            rep = run_quiver_cell(mp, pathlib.Path(args.out))
            if "error" in rep:
                failures += 1
        raise SystemExit(failures)

    archs = sorted(all_configs()) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    out_dir = pathlib.Path(args.out)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rep = run_cell(arch, shape, mp, out_dir)
                if "error" in rep:
                    failures += 1
                    print(f"[dryrun] FAIL {rep['cell']}: {rep['error']}")
                elif "skipped" in rep:
                    print(f"[dryrun] SKIP {rep['cell']}: {rep['skipped']}")
                else:
                    print(f"[dryrun] OK   {rep['cell']} "
                          f"(compile {rep['compile_seconds']}s)")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
