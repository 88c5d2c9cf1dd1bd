"""Roofline analysis from compiled XLA artifacts (no hardware needed).

Sources:
  * ``compiled.cost_analysis()``   -> per-device HLO FLOPs + bytes accessed
    (calibrated: on an N-way SPMD program these are per-device numbers).
  * ``compiled.as_text()``         -> post-partitioning optimized HLO; we
    parse every collective op (shapes are per-device) for collective bytes.
  * ``compiled.memory_analysis()`` -> per-device argument/output/temp bytes.

Hardware model: TPU v5e —
  197 TFLOP/s bf16 / chip, 819 GB/s HBM / chip, ~50 GB/s/link ICI.

Terms (seconds, per the assignment formulas; collective bytes parsed from
the per-device SPMD module so chips cancels):
  compute    = HLO_FLOPs_per_device / peak
  memory     = HLO_bytes_per_device / hbm_bw
  collective = wire_bytes_per_device / link_bw
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
LINK_BW = 50e9           # bytes/s / ICI link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

# `= <result-type> <op>(` where op may be the async `-start` variant.
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_TILED_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    bytes_naive: Dict[str, int] = field(default_factory=dict)  # Σ result sizes
    bytes_wire: Dict[str, float] = field(default_factory=dict)  # ring estimate

    @property
    def total_naive(self) -> int:
        return sum(self.bytes_naive.values())

    @property
    def total_wire(self) -> float:
        return sum(self.bytes_wire.values())

    def as_dict(self) -> Dict:
        return {"counts": self.counts, "bytes_naive": self.bytes_naive,
                "bytes_wire": self.bytes_wire,
                "total_naive": self.total_naive,
                "total_wire": self.total_wire}


def _group_size(line: str) -> int:
    m = _GROUPS_TILED_RE.search(line)
    if m:
        return int(m.group(2))          # [n_groups, group_size]<=[N]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _wire_factor(op: str, g: int) -> float:
    """Ring-algorithm bytes-on-wire per participating device, as a factor of
    the *result* buffer size."""
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (g - 1) / g
    if op == "all-gather":
        return (g - 1) / g              # result is the gathered (big) buffer
    if op == "reduce-scatter":
        return float(g - 1)             # result is the scattered (small) one
    if op == "all-to-all":
        return (g - 1) / g
    if op == "collective-permute":
        return 1.0
    return 1.0


def parse_collectives(hlo_text: str) -> CollectiveStats:
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        nbytes = _type_bytes(type_str)
        g = _group_size(line)
        st.counts[op] = st.counts.get(op, 0) + 1
        st.bytes_naive[op] = st.bytes_naive.get(op, 0) + nbytes
        st.bytes_wire[op] = (st.bytes_wire.get(op, 0.0)
                             + nbytes * _wire_factor(op, g))
    return st


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    coll: CollectiveStats
    chips: int
    model_flops: float = 0.0            # 6·N·D (or 2·N·D inference), global
    xla_flops: float = 0.0              # raw cost_analysis (loop bodies x1)
    xla_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll.total_wire / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total compiled FLOPs — remat/redundancy waste."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs utilisation at the bound: what MFU would be if the
        dominant term ran at peak (the score we hillclimb)."""
        if self.bound_s <= 0:
            return 0.0
        return (self.model_flops / self.chips / PEAK_FLOPS) / self.bound_s

    def as_dict(self) -> Dict:
        return {
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.coll.as_dict(),
        }


def analyze(compiled, *, chips: int, model_flops: float = 0.0,
            discount_scope: Optional[str] = None,
            extra_bytes_per_device: float = 0.0) -> Roofline:
    """Roofline terms from the compiled SPMD module.

    FLOPs/bytes/collectives come from the trip-count-aware HLO walker
    (``repro.telemetry.hlo_cost``) — XLA's ``cost_analysis()`` counts while
    bodies once, which under a layers-scan is wrong by ~n_layers.  The raw
    XLA numbers are retained as ``xla_*`` for cross-checking loop-free
    programs.

    ``discount_scope``: zero out HBM bytes of named_scope-marked regions
    that execute as single Pallas kernels on the TPU target; the caller
    adds the kernel boundary traffic via ``extra_bytes_per_device``
    (see :func:`fused_boundary_bytes`)."""
    from repro.telemetry import hlo_cost

    ca = compiled.cost_analysis() or {}
    totals = hlo_cost.analyze_text(compiled.as_text(),
                                   discount_scope=discount_scope)
    coll = CollectiveStats(
        counts={k: int(v) for k, v in totals.coll_counts.items()},
        bytes_naive={k: int(v) for k, v in totals.coll_bytes_naive.items()},
        bytes_wire=dict(totals.coll_bytes_wire))
    return Roofline(flops_per_device=totals.flops,
                    bytes_per_device=totals.bytes + extra_bytes_per_device,
                    coll=coll, chips=chips, model_flops=model_flops,
                    xla_flops=float(ca.get("flops", 0.0)),
                    xla_bytes=float(ca.get("bytes accessed", 0.0)))


def fused_boundary_bytes(cfg, shape, chips: int, *,
                         act_bytes: int = 2) -> float:
    """Per-device HBM boundary traffic of the fused attention kernels.

    Flash fwd reads q,k,v and writes o per layer; the bwd kernel reads
    q,k,v,o,do and writes dq,dk,dv (factor ~3.5 total for training).
    Decode reads the KV cache (the fundamental term) + writes one token.
    """
    hd = cfg.resolved_head_dim
    n_attn = sum(1 for k in cfg.layer_kinds() if k != "mamba")
    if n_attn == 0:
        return 0.0
    h, kv = cfg.n_heads, cfg.n_kv_heads
    if shape.kind in ("train", "prefill"):
        per_token = (2 * h + 2 * kv) * hd * act_bytes   # q+o + k+v
        mult = 3.5 if shape.kind == "train" else 1.0
        total = (n_attn * shape.global_batch * shape.seq_len
                 * per_token * mult)
        if cfg.n_encoder_layers:                        # cross + encoder
            total *= 2
        return total / chips
    # decode: each step reads the whole (windowed) cache per layer
    kl = shape.seq_len
    if cfg.swa_window:
        kl = min(kl, cfg.swa_window)
    elif cfg.family == "hybrid":
        kl = min(kl, 4096)
    cache = n_attn * shape.global_batch * kl * 2 * kv * hd * act_bytes
    return cache / chips


def memory_stats(compiled) -> Dict[str, int]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def model_flops_for(cfg, shape, n_params_active: Optional[int] = None) -> float:
    """6·N·D train / 2·N·D single forward, D = global tokens this step."""
    n = n_params_active if n_params_active is not None else cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence per step
    return 2.0 * n * shape.global_batch
