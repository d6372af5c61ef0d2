"""Roofline term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (TPU v5e constants):

    compute    = HLO_FLOPs_per_device / peak_FLOPs          (197 TF/s bf16)
    memory     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
    collective = collective_bytes_per_device / ICI_bw        (~50 GB/s/link)

``cost_analysis`` on the SPMD-partitioned module reports *per-device* flops
and bytes. Collective bytes are not in cost_analysis: we parse the
post-partition HLO and sum result-shape bytes of every collective op
(all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute).
That counts each op's per-device payload once — a conservative single-link
model; multi-link meshes only scale the constant, not the *shape* of the
analysis, and the hillclimb optimizes relative deltas.
"""
from __future__ import annotations

import re

HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # per chip
    "ici_bw": 50e9,              # per link (single-link model)
}

#: Published per-chip HBM bandwidth, keyed by ``jax.Device.device_kind``
#: (the string JAX reports). Source: Google Cloud documentation, "TPU v5e"
#: — 16 GB of HBM at 819 GB/s per chip. A device that is not in the table
#: has no peak: :func:`peak_bandwidth` raises rather than guess one.
PEAK_HBM_BW = {
    "TPU v5 lite": (HW["hbm_bw"], "Google Cloud documentation, TPU v5e"),
}


def peak_bandwidth(device_kind: str | None = None) -> dict:
    """``{device_kind, bw_bytes_per_s, source}`` — the published HBM peak
    of ``device_kind`` (default: the first visible device's), the
    denominator of an achieved-vs-peak fraction. Raises ``KeyError`` for
    a device with no published peak in :data:`PEAK_HBM_BW`."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_HBM_BW:
        raise KeyError(
            f"no published HBM peak for device_kind={device_kind!r}; "
            f"known: {sorted(PEAK_HBM_BW)}")
    bw, src = PEAK_HBM_BW[device_kind]
    return {"device_kind": device_kind, "bw_bytes_per_s": bw,
            "source": src}


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "f32[16,1024]" or "bf16[8,128]{1,0}"
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * b


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-type {bytes, count} from post-partition HLO text."""
    stats = {c: {"bytes": 0, "count": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        for coll in _COLLECTIVES:
            # opcode appears right after the result shape, before '('
            om = re.search(r"\)?\s(" + coll + r")\(", rhs) or \
                re.match(r"^\(?.*?\s" + coll + r"\(", rhs)
            if f" {coll}(" in rhs or rhs.startswith(coll + "("):
                # result shapes = all shapes before the opcode token
                head = rhs.split(coll + "(")[0]
                nbytes = sum(_shape_bytes(d, s)
                             for d, s in _SHAPE_RE.findall(head))
                # fusion/computation shapes can sneak in; result shape(s)
                # always lead the rhs, so cap at the leading tuple
                stats[coll]["bytes"] += nbytes
                stats[coll]["count"] += 1
                break
    total = sum(v["bytes"] for v in stats.values())
    stats["total_bytes"] = total
    return stats


def roofline_terms(cost: dict, coll_bytes: int, model_flops_global: float,
                   n_chips: int) -> dict:
    """cost: compiled.cost_analysis() dict (per-device)."""
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    t_compute = flops / HW["peak_flops_bf16"]
    t_memory = bytes_accessed / HW["hbm_bw"]
    t_coll = coll_bytes / HW["ici_bw"]
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_coll)), key=lambda kv: kv[1])[0]
    bound = max(t_compute, t_memory, t_coll)
    useful = model_flops_global / n_chips
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_bytes,
        "model_flops_per_device": useful,
        "useful_flops_ratio": useful / flops if flops else 0.0,
        # fraction of the roofline bound spent doing useful model math
        "roofline_fraction": (useful / HW["peak_flops_bf16"]) / bound
        if bound else 0.0,
    }


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode counts one token/seq.

    N counts *matmul-participating* params: the input-embedding table is a
    gather (0 FLOPs) and is excluded; the LM-head matmul is included. For
    tied embeddings ``param_count`` already counts the table once (and it
    does participate in the head matmul), so no correction applies there.
    """
    n_active = cfg.active_param_count()
    if not cfg.tie_embeddings:
        n_active -= cfg.vocab * cfg.d_model   # input embedding: gather only
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
