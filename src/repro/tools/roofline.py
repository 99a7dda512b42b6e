"""Roofline-term extraction from compiled XLA artifacts.

Three terms per (arch x shape x mesh), all in seconds (per-step):

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

``cost_analysis()`` on an SPMD-partitioned module reports *per-device*
flops/bytes, so the "chips x" in the roofline denominators is already
applied.  Collective bytes are not in cost_analysis: we parse the
optimized HLO and sum the result-shape bytes of every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute op
(per-device, one-shot convention; ring-factor 2(n-1)/n refinements are
noted in EXPERIMENTS.md where they matter).

Hardware model: :data:`PEAKS`, keyed by ``device_kind`` (as jax
reports it).  A kind that is not in the table is an error, never a
default: peaks of one chip are not a stand-in for another's.
"""

from __future__ import annotations

import re
from typing import Any

# Published per-chip peaks (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip
# (~50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,     # bf16 / chip
        "hbm_bw": 819e9,          # bytes/s / chip
        "ici_bw": 50e9,           # bytes/s / link
    },
}


def peaks_for(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; raises for a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * size


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, Any]:
    """Sum result bytes per collective kind from optimized HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # result type(s) appear between '=' and the op name
        for kind in _COLLECTIVES:
            marker = f" {kind}("
            alt = f" {kind}-start("
            if marker in stripped or alt in stripped:
                eq = stripped.find("=")
                op_at = stripped.find(marker)
                if op_at < 0:
                    op_at = stripped.find(alt)
                if eq < 0 or op_at < eq:
                    continue
                result_sig = stripped[eq + 1: op_at]
                total = sum(
                    _shape_bytes(d, s)
                    for d, s in _SHAPE_RE.findall(result_sig)
                )
                out[kind] += total
                counts[kind] += 1
                break
    return {
        "bytes_by_kind": out,
        "counts_by_kind": counts,
        "total_bytes": sum(out.values()),
        "total_count": sum(counts.values()),
    }


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return dict(ca) if ca else {}


def analyze(compiled, *, n_chips: int, model_flops: float,
            device_kind: str,
            jaxpr_costs: dict | None = None) -> dict:
    """Roofline report dict for one compiled executable.

    ``jaxpr_costs`` (from ``repro.tools.jaxpr_cost``) provides the
    scan-corrected global FLOPs/bytes; XLA's cost_analysis (which counts
    loop bodies once) is retained for cross-reference only.  Collective
    bytes come from the optimized HLO with while-trip-count correction
    (``repro.tools.hlo_collectives``).  ``device_kind`` names the chip
    whose peaks bound the terms (:func:`peaks_for`).
    """
    hw = peaks_for(device_kind)
    cost = _cost_dict(compiled)
    xla_flops_dev = float(cost.get("flops", 0.0))
    xla_bytes_dev = float(cost.get("bytes accessed", 0.0))

    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    from repro.tools.hlo_collectives import parse_collectives
    coll = parse_collectives(hlo)

    if jaxpr_costs is not None:
        flops_dev = jaxpr_costs["flops"] / n_chips
        bytes_dev = jaxpr_costs["bytes"] / n_chips
    else:
        flops_dev = xla_flops_dev
        bytes_dev = xla_bytes_dev

    compute_s = flops_dev / hw["peak_flops"]
    memory_s = bytes_dev / hw["hbm_bw"]
    collective_s = coll["total_bytes"] / hw["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    bound_s = max(terms.values())
    total_hlo_flops = flops_dev * n_chips

    mem_an = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem_an = {
                k: int(getattr(ma, k))
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                )
                if hasattr(ma, k)
            }
    except Exception:
        pass

    return {
        "n_chips": n_chips,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "xla_flops_per_device": xla_flops_dev,
        "xla_bytes_per_device": xla_bytes_dev,
        "collectives": coll,
        "terms_seconds": terms,
        "dominant": dominant,
        "bound_seconds": bound_s,
        "model_flops": model_flops,
        "useful_flops_ratio": (
            model_flops / total_hlo_flops if total_hlo_flops else 0.0
        ),
        "mfu_at_bound": (
            model_flops / (n_chips * hw["peak_flops"] * bound_s)
            if bound_s else 0.0
        ),
        "memory_analysis": mem_an,
    }


def model_flops_for(cfg, shape) -> float:
    """6·N_active·tokens for training, 2·N_active·tokens for inference."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one new token per sequence
    return 2.0 * n * shape.global_batch
