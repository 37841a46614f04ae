"""Three-term roofline model from a compiled dry-run artifact.

Terms (per step, whole mesh):
  compute    = HLO_FLOPs / (chips × peak_FLOP/s)
  memory     = HLO_bytes / (chips × HBM_bw)
  collective = collective_bytes / (chips × link_bw)

Sources: ``compiled.cost_analysis()`` provides flops and bytes accessed;
collective bytes are NOT in cost_analysis — we parse the optimized HLO
(``compiled.as_text()``) and sum the *output* shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op (output size is the per-device payload each device must receive — the
standard bandwidth-term convention).

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM
(``PEAKS``, with its source), ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

@dataclass(frozen=True)
class ChipPeaks:
    """Published peak rates of one chip."""
    hbm_bytes_per_s: float
    bf16_flops: float
    int8_ops: float


#: peak rates per chip, keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" —
#: 16 GB of HBM at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8.
#: "cpu" is one explicit nominal row for tests and the tuner on CPU hosts:
#: bandwidth set generously high so the tuner's bandwidth-bound pruning
#: (engine/tuner.py) never rejects a candidate there; nothing measured
#: against it is a device number.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(hbm_bytes_per_s=819e9, bf16_flops=197e12,
                             int8_ops=393e12),
    "cpu": ChipPeaks(hbm_bytes_per_s=1.0e11, bf16_flops=1.0e12,
                     int8_ops=1.0e12),
}

# -- the dry-run roofline's hardware model (TPU v5e) -------------------------
PEAK_FLOPS = PEAKS["TPU v5 lite"].bf16_flops     # bf16 FLOP/s per chip
HBM_BW = PEAKS["TPU v5 lite"].hbm_bytes_per_s    # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link per chip
VMEM_BYTES = 16 * 2**20      # default scoped VMEM limit per kernel (v5e);
                             # the engine's tile-feasibility bound
                             # (engine/kernels.py) prunes candidate plans
                             # whose modeled working set exceeds it


def device_kind() -> str:
    """The peak-table key of JAX's first device: its ``device_kind`` on a
    TPU, else its platform name ("cpu")."""
    import jax
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else dev.platform


def peaks(kind: Optional[str] = None) -> ChipPeaks:
    """Peak rates of ``kind`` (None: this process's device). A device the
    table does not know is an error, never a default."""
    kind = device_kind() if kind is None else kind
    if kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {kind!r}: add its "
                       f"published figures to analysis/roofline.py PEAKS")
    return PEAKS[kind]


def peak_bytes_per_s(kind: Optional[str] = None) -> float:
    """Peak memory bandwidth of ``kind`` (None: this process's device)."""
    return peaks(kind).hbm_bytes_per_s


def achieved_fraction(bytes_touched: float, wall_s: float, *,
                      kind: Optional[str] = None) -> float:
    """Fraction of the device's peak bandwidth a measured run achieved.

    ``bytes_touched / wall_s / peak`` — the roofline-verification number
    the megakernel bench reports per cell: how close the answer step runs
    to the memory roof the predicted-bytes model says it must pay.
    """
    if wall_s <= 0:
        return 0.0
    return bytes_touched / wall_s / peak_bytes_per_s(kind)


_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                   "all-to-all", "collective-permute")

# e.g.  %x = bf16[4,128,256]{2,1,0} all-gather(...)
_SHAPE_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum output-shape bytes of every collective op in optimized HLO."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        kind = None
        for k in _COLLECTIVE_OPS:
            # match the op name at the call position: "... = shape op-name("
            if f" {k}(" in s or f" {k}-start(" in s or f" {k}-done(" in s:
                kind = k
                break
        if kind is None:
            continue
        if f" {kind}-done(" in s:
            continue            # -start already counted the payload
        m = _SHAPE_RE.search(s)
        if not m:
            continue
        dtype, dims = m.group(1), m.group(2)
        b = shape_bytes(dtype, dims)
        # tuple-shaped outputs: count every element shape on the line
        if "(" in s.split("=")[1].split(kind)[0]:
            b = 0
            for dt, dm in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]",
                                     s.split(f" {kind}")[0]):
                if dt in _DTYPE_BYTES:
                    b += shape_bytes(dt, dm)
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    name: str
    n_chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float           # 6·N·D (or 6·N_active·D) per step
    collectives: Optional[CollectiveStats] = None
    hlo_elem_flops: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.n_chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.n_chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.n_chips * LINK_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline bound."""
        if self.step_time == 0:
            return 0.0
        return self.model_flops / (self.step_time * self.n_chips
                                   * PEAK_FLOPS)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "n_chips": self.n_chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "hlo_elem_flops": self.hlo_elem_flops,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_step_s": self.step_time,
            "useful_flop_ratio": self.useful_flop_ratio,
            "mfu_bound": self.mfu,
            "collective_breakdown": (self.collectives.bytes_by_kind
                                     if self.collectives else {}),
        }


def cost_totals(cost: dict) -> Dict[str, float]:
    """Normalize cost_analysis output (it may be a dict or list of dicts)."""
    if isinstance(cost, (list, tuple)):
        merged: Dict[str, float] = {}
        for c in cost:
            for k, v in c.items():
                merged[k] = merged.get(k, 0.0) + v
        cost = merged
    return cost


def model_flops_for(n_params: int, n_tokens: int, *, training: bool) -> float:
    """6·N·D for a train step, 2·N·D for inference (per forward token)."""
    factor = 6.0 if training else 2.0
    return factor * n_params * n_tokens


def from_compiled(name: str, compiled, *, n_chips: int, model_flops: float,
                  hlo_text: Optional[str] = None) -> Roofline:
    """Roofline terms from the compiled artifact.

    Uses the loop-aware analyzer (analysis/hlo_cost.py) because XLA's
    builtin ``cost_analysis()`` counts ``while`` bodies once — a 61-layer
    scan would be undercounted ~100×. Totals are per-device per-step
    (post-SPMD shapes); the roofline terms divide by per-chip rates, so
    per-device totals are exactly what the terms want.
    """
    from repro.analysis import hlo_cost
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_cost.analyze(text)
    stats = CollectiveStats(bytes_by_kind={k: int(v) for k, v
                                           in cost.coll_by_kind.items()})
    return Roofline(name=name, n_chips=n_chips,
                    hlo_flops=cost.flops * n_chips,
                    hlo_bytes=cost.bytes * n_chips,
                    collective_bytes=cost.coll_bytes * n_chips,
                    model_flops=model_flops, collectives=stats,
                    hlo_elem_flops=cost.elem_flops * n_chips)


def format_table(rows: List[dict]) -> str:
    """Markdown table for EXPERIMENTS.md §Roofline."""
    hdr = ("| cell | chips | t_compute | t_memory | t_collective | "
           "bottleneck | useful/HLO | MFU-bound |")
    sep = "|" + "---|" * 8
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['name']} | {r['n_chips']} | {_fmt_s(r['t_compute_s'])} "
            f"| {_fmt_s(r['t_memory_s'])} | {_fmt_s(r['t_collective_s'])} "
            f"| {r['bottleneck']} | {r['useful_flop_ratio']:.2f} "
            f"| {r['mfu_bound']*100:.1f}% |")
    return "\n".join(out)


def _fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:.2f} s"
    if x >= 1e-3:
        return f"{x*1e3:.2f} ms"
    return f"{x*1e6:.1f} µs"
