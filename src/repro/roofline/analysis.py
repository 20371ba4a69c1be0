"""Three-term roofline analysis from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

Hardware constants come from ``PEAKS``, one row per ``device_kind``
with its published source; a device kind missing from the table is an
error, never a silent default.

Collective bytes are NOT in cost_analysis: we parse the post-SPMD
``compiled.as_text()`` and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

Scan bodies appear ONCE in the HLO text and in ``cost_analysis`` even though
they execute ``n_groups`` times — the dry-run therefore lowers each step at
two reduced depths (G=2 and G=4) and extrapolates linearly:
  per_group = (T(4) - T(2)) / 2;   total(G) = T(2) + (G - 2) * per_group.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" is the TPU v5e — Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600
#: Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,   # bf16 FLOP/s per chip
        "peak_int8_ops": 393e12,
        "hbm_bw": 819e9,        # bytes/s per chip
        "hbm_bytes": 16e9,
        "ici_bw": 50e9,         # bytes/s per link
    },
}

#: The part the analytic models (SLA provisioning, dry-run rooflines)
#: target when the caller names none: the v5e this repo is measured on.
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_DEVICE_KIND) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a sourced row to "
            "repro.roofline.analysis.PEAKS")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V1_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return default


def _ring_traffic(kind: str, out_bytes: int, gs: int) -> float:
    """Bytes crossing each device's link for one ring execution.

    Sizes come from the op's *output* in the partitioned (per-device)
    module: all-gather output is the gathered (full) tensor, all-reduce
    output the full partial, reduce-scatter output the local shard.
    """
    if gs <= 1:
        return 0.0
    if kind == "all-gather":
        return out_bytes * (gs - 1) / gs
    if kind == "all-reduce":
        return 2.0 * out_bytes * (gs - 1) / gs
    if kind == "reduce-scatter":
        return float(out_bytes * (gs - 1))
    if kind == "all-to-all":
        return out_bytes * (gs - 1) / gs
    return float(out_bytes)  # collective-permute


def parse_collectives(hlo_text: str, default_group: int = 256) -> Dict:
    """Collective schedule from post-SPMD HLO: per-kind output bytes,
    counts, and per-link ring traffic (bytes through each chip's link)."""
    per_kind = Counter()
    counts = Counter()
    traffic = Counter()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        kind = m.group(1)
        # output shape(s) precede the op name; for (operand, result)
        # tuples of async starts, the result is the last shape.
        shapes = list(_SHAPE_RE.finditer(m.group(0)))
        if not shapes:
            continue
        out_bytes = _shape_bytes(shapes[-1].group(1), shapes[-1].group(2))
        gs = _group_size(line, default_group)
        per_kind[kind] += out_bytes
        counts[kind] += 1
        traffic[kind] += _ring_traffic(kind, out_bytes, gs)
    return {"bytes_by_kind": dict(per_kind),
            "counts": dict(counts),
            "link_traffic_by_kind": {k: float(v) for k, v in traffic.items()},
            "total_bytes": sum(per_kind.values()),
            "total_link_traffic": float(sum(traffic.values()))}


def roofline_terms(flops: float, bytes_accessed: float,
                   collective_bytes: float, n_chips: int,
                   device_kind: str = TARGET_DEVICE_KIND) -> Dict:
    """flops / bytes_accessed are GLOBAL (summed over chips);
    collective_bytes is global link traffic (per-link traffic x chips) so
    the spec formula collective_bytes/(chips*link_bw) equals per-link time.
    """
    hw = peaks(device_kind)
    t_comp = flops / (n_chips * hw["peak_flops"])
    t_mem = bytes_accessed / (n_chips * hw["hbm_bw"])
    t_coll = collective_bytes / (n_chips * hw["ici_bw"])
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_comp, t_mem, t_coll)
    terms.update(
        dominant=dom.replace("_s", ""),
        step_time_s=bound,
        # fraction of the roofline-limited time spent doing useful compute
        roofline_fraction=(t_comp / bound) if bound > 0 else 0.0,
    )
    return terms


def fused_delta_footprint(lowered, shards: int = 1) -> Dict:
    """Analytic per-beat footprint of the fused delta mega-kernel.

    Counts the bytes moved and integer compare-ops one steady-state
    delta beat pays through ``backend.fused_delta``, from the lowered
    plan's static geometry (worst case: every stage's admission pane at
    its full ``delta_words`` span and every dirty set at ``dirty_cap``).
    Three phases per the kernel contract (kernels/fused_delta.py):

      pane   — re-admit ALL T rows against the A-word changed pane:
               reads cols [C,T] + pane bounds [C, 32A]x2, read-merges
               the [T, A] carry slice; 2*T*C*32A compares.
      dirty  — re-scan the D dirty rows against the FULL Q-slot window:
               reads [C,D] gathered cols + [C,Q] bounds x2, scatters
               [D, Q/32] words; 2*D*C*Q compares.
      probe  — each dirty spine row probes ONE bucket pane of width B:
               reads D keys + [D,B] bucket keys/rows, scatters D rids;
               2*D*B compares.

    ``shards`` divides the row-proportional terms (T and D are
    shard-local under the row mesh; probe sides are replicated).
    Feeds ``roofline_terms`` so BENCH_PR6.json can report whether the
    fused beat is memory- or compute-bound on the target part.
    """
    schemas = lowered.plan.catalog.schemas
    bytes_total, iops_total, per_stage = 0.0, 0.0, []
    for st in lowered.scans:
        if not st.cols or not st.covered.any():
            continue
        C, Q, A = len(st.cols), st.q_window, st.delta_words
        T = -(-schemas[st.table].capacity // shards)
        D = min(schemas[st.table].dirty_cap, T)
        b = (T * C * 4 + 2 * C * A * 32 * 4 + 2 * T * A * 4
             + D * C * 4 + 2 * C * Q * 4 + D * (Q // 32) * 8)
        i = 2.0 * T * C * A * 32 + 2.0 * D * C * Q
        per_stage.append({"stage": f"scan:{st.table}", "bytes": b,
                          "int_ops": i})
        bytes_total, iops_total = bytes_total + b, iops_total + i
    for j in lowered.joins:
        if j.kind == "gather":
            continue
        D = min(schemas[j.spine].dirty_cap,
                -(-schemas[j.spine].capacity // shards))
        B = j.bucket_cap if j.kind == "partitioned" \
            else schemas[j.pk_table].capacity
        b = D * 4 + D * B * 8 + D * 8
        i = 2.0 * D * B
        per_stage.append({"stage": f"probe:{j.spine}->{j.pk_table}",
                          "bytes": b, "int_ops": i})
        bytes_total, iops_total = bytes_total + b, iops_total + i
    terms = roofline_terms(iops_total, bytes_total, 0.0, max(shards, 1))
    return {"per_stage": per_stage, "bytes": float(bytes_total),
            "int_ops": float(iops_total),
            "arith_intensity": iops_total / max(bytes_total, 1.0),
            "dominant": terms["dominant"],
            "roofline_fraction": terms["roofline_fraction"]}


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D inference (D = tokens).

    N excludes the input-embedding gather (not a matmul); the unembedding
    projection IS a matmul and stays counted (for tied embeddings the single
    table is the unembedding matmul, so nothing is subtracted).
    """
    n_active = cfg.active_param_count()
    if not cfg.tie_embeddings:
        n_active -= cfg.vocab_padded() * cfg.d_model  # gather-only table
    if shape.kind == "train":
        tokens = shape.global_batch * (
            shape.seq_len // cfg.dec_ratio if cfg.enc_dec else shape.seq_len)
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * (
            shape.seq_len // cfg.dec_ratio if cfg.enc_dec else shape.seq_len)
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # one token per sequence
