"""Kernel registry: descriptors over the answer-kernel bodies + feasibility.

The engine's inventory of *how an answer step can run*. Each
:class:`KernelDescriptor` wraps one existing kernel body — the materialized
select-XOR scan (jnp oracle / Pallas ``dpxor``), the fused chunked
expand+scan, the additive int8 GEMM (jnp dot / Pallas ``pir_matmul``), and
the standalone GGM level expansion — and declares:

  * its **tunable-parameter space** (the tile sizes that used to be
    hardcoded constants in ``kernels/ops.py``), already normalized to
    *legal* tiles for the concrete problem shape (``backend.legal_tile``),
  * a **VMEM-footprint model** (``analysis/roofline.py`` constants): the
    per-grid-step working set in bytes, streamed blocks counted twice for
    Pallas's double-buffered pipeline. Candidates whose footprint exceeds
    ``VMEM_BYTES`` are pruned *without running* — the tuner never wastes
    budget timing a plan Mosaic would refuse to schedule,
  * a **predicted-bytes model**: HBM traffic of one answer step, the
    memory-roofline numerator that plan reports surface next to each
    chosen plan.

Serve-path descriptors (``serve=True``) emit ``ExecutionPlan`` candidates;
the GGM expansion is registered ``serve=False`` — it is tuned standalone
(``tuner.tune_standalone``) because DPF evaluation happens inside the
protocol's ``answer_local``, not as a separately planned stage.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.roofline import VMEM_BYTES
from repro.engine.backend import legal_tile

U32_BYTES = 4


@dataclass(frozen=True)
class ProblemShape:
    """The concrete shapes one plan candidate must serve.

    bucket      Q — padded query-batch size (one compiled bucket)
    rows        R — rows held by ONE DB shard (n_items / n_shards)
    item_bytes  L — record payload bytes (words = L / 4)
    """
    bucket: int
    rows: int
    item_bytes: int

    @property
    def words(self) -> int:
        return self.item_bytes // 4

    @property
    def log_rows(self) -> int:
        return (self.rows - 1).bit_length()


@dataclass(frozen=True)
class KernelDescriptor:
    """One answer-kernel body + its tunable space and validity model."""

    name: str
    share_kind: str                       # xor | additive | lwe | prg
    #: ExecutionPlan base fields (serve kernels); empty for standalone
    expand: str = ""
    scan: str = ""
    #: shape -> {param: candidate values}, already legal for that shape
    space_fn: Callable[[ProblemShape], Dict[str, Tuple[int, ...]]] = \
        field(default=lambda s: {})
    #: shape, params -> params with *coupled* constraints applied (e.g.
    #: the megakernel's chunk_log <= log2(tile_r)); runs before dedup so
    #: two requests that legalize identically are measured once
    legalize_fn: Callable[[ProblemShape, Dict[str, int]], Dict[str, int]] = \
        field(default=lambda s, p: p)
    #: shape, params -> per-grid-step VMEM working set (bytes)
    footprint_fn: Callable[[ProblemShape, Dict[str, int]], int] = \
        field(default=lambda s, p: 0)
    #: shape, params -> HBM bytes moved by one answer step (reporting)
    bytes_fn: Callable[[ProblemShape, Dict[str, int]], int] = \
        field(default=lambda s, p: 0)
    serve: bool = True
    #: False for a body the TPU compiler refuses (Mosaic has no int32
    #: matmul on v5e): the tuner never offers it on a TPU backend
    mosaic: bool = True

    def feasible(self, shape: ProblemShape, params: Dict[str, int]) -> bool:
        return self.footprint_fn(shape, params) <= VMEM_BYTES

    def candidates(self, shape: ProblemShape,
                   max_candidates: Optional[int] = None
                   ) -> List[Dict[str, int]]:
        """Feasible parameter assignments, deduped after legalization.

        Two requested tiles can legalize to the same effective tile on a
        small shape (e.g. 512 and 2048 both collapse to R=64); duplicates
        are measured once. ``max_candidates`` is the per-kernel budget cap
        (the CI smoke runs with 2).
        """
        space = self.space_fn(shape)
        names = sorted(space)
        combos = itertools.product(*(space[n] for n in names)) \
            if names else [()]
        seen, out = set(), []
        for combo in combos:
            params = self.legalize_fn(shape, dict(zip(names, combo)))
            key = tuple(sorted(params.items()))
            if key in seen or not self.feasible(shape, params):
                continue
            seen.add(key)
            out.append(params)
            if max_candidates is not None and len(out) >= max_candidates:
                break
        return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

KERNELS: Dict[str, KernelDescriptor] = {}


def register_kernel(desc: KernelDescriptor) -> KernelDescriptor:
    KERNELS[desc.name] = desc
    return desc


def serve_kernels(share_kind: str, backend: Optional[str] = None
                  ) -> List[KernelDescriptor]:
    """Serve-path descriptors for one share algebra, registry order;
    with ``backend="tpu"``, only the bodies the TPU compiler accepts."""
    return [d for d in KERNELS.values()
            if d.serve and d.share_kind == share_kind
            and (d.mosaic or backend != "tpu")]


def get_kernel(name: str) -> KernelDescriptor:
    if name not in KERNELS:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(KERNELS)}")
    return KERNELS[name]


# ---------------------------------------------------------------------------
# Descriptor bodies: spaces, VMEM footprints, byte models
# ---------------------------------------------------------------------------
# Requested tile ladders (the pre-engine hardcoded constants are members,
# so the heuristic plan is always inside the search space).
_DPXOR_TILES = (512, 1024, 2048, 4096)
_GEMM_TILE_Q = (8, 16)
_GEMM_TILE_R = (512, 1024, 2048)
_GEMM_TILE_L = (128, 256)
_FUSED_CHUNK_LOGS = (8, 10, 12, 14)
_GGM_TILES = (512, 2048, 8192, 65536)

#: the GEMM reduction-tile default before tiles moved into the plan
#: (``kernels/ops.py pir_gemm`` hardcoded 1024 vs the scan's 2048)
GEMM_TILE_R_DEFAULT = 1024


def _xor_scan_space(shape: ProblemShape) -> Dict[str, Tuple[int, ...]]:
    tiles = sorted({legal_tile(shape.rows, t, pow2=True)
                    for t in _DPXOR_TILES})
    return {"tile_r": tuple(tiles)}


def _xor_scan_footprint(shape: ProblemShape, p: Dict[str, int]) -> int:
    q, w = shape.bucket, shape.words
    tr = p.get("tile_r", legal_tile(shape.rows, 2048, pow2=True))
    # streamed blocks ×2 (double buffer): bits [Q,TR] + db [W,TR];
    # resident: accumulator [Q,W] + the masked intermediate [Q,W,TR]
    return U32_BYTES * (2 * (q * tr + w * tr) + q * w + q * w * tr)


def _xor_mat_bytes(shape: ProblemShape, p: Dict[str, int],
                   *, pallas: bool) -> int:
    q, r, w = shape.bucket, shape.rows, shape.words
    bits = 2 * q * r * U32_BYTES          # materialized: written then read
    db = (1 if pallas else q) * r * w * U32_BYTES   # jnp vmap re-reads/query
    return bits + db + q * w * U32_BYTES


def _fused_space(shape: ProblemShape) -> Dict[str, Tuple[int, ...]]:
    # chunks larger than the shard are degenerate duplicates (n_chunks=1)
    logs = sorted({min(c, shape.log_rows) for c in _FUSED_CHUNK_LOGS})
    return {"chunk_log": tuple(logs)}


def _fused_footprint(shape: ProblemShape, p: Dict[str, int]) -> int:
    chunk = 1 << p.get("chunk_log", 12)
    # per-chunk working set: db rows + selection bits (never hit HBM)
    return U32_BYTES * chunk * (2 * shape.words + 1)


def _fused_bytes(shape: ProblemShape, p: Dict[str, int]) -> int:
    # every query streams the whole shard once; bits stay on-chip
    return (shape.bucket * shape.rows * shape.words + shape.bucket
            * shape.words) * U32_BYTES


def _gemm_space(shape: ProblemShape) -> Dict[str, Tuple[int, ...]]:
    return {
        "tile_q": tuple(sorted({legal_tile(shape.bucket, t)
                                for t in _GEMM_TILE_Q})),
        "tile_r": tuple(sorted({legal_tile(shape.rows, t)
                                for t in _GEMM_TILE_R})),
        "tile_l": tuple(sorted({legal_tile(shape.item_bytes, t)
                                for t in _GEMM_TILE_L})),
    }


def _gemm_footprint(shape: ProblemShape, p: Dict[str, int]) -> int:
    tq = p.get("tile_q", legal_tile(shape.bucket, 8))
    tr = p.get("tile_r", legal_tile(shape.rows, GEMM_TILE_R_DEFAULT))
    tl = p.get("tile_l", legal_tile(shape.item_bytes, 128))
    # int8 streamed blocks ×2; int32 output block resident
    return 2 * (tq * tr + tr * tl) + 4 * tq * tl


def _gemm_bytes(shape: ProblemShape, p: Dict[str, int]) -> int:
    q, r, l = shape.bucket, shape.rows, shape.item_bytes
    # shares materialized (write+read, int8) + one DB pass + int32 out
    return 2 * q * r + r * l + 4 * q * l


def _lwe_gemm_footprint(shape: ProblemShape, p: Dict[str, int]) -> int:
    tq = p.get("tile_q", legal_tile(shape.bucket, 8))
    tr = p.get("tile_r", legal_tile(shape.rows, GEMM_TILE_R_DEFAULT))
    tl = p.get("tile_l", legal_tile(shape.item_bytes, 128))
    # int32 everywhere: streamed ct/db blocks ×2 + resident output block.
    # 4× the int8 GEMM's streams — the same tile ladder prunes earlier.
    return 4 * (2 * (tq * tr + tr * tl) + tq * tl)


def _lwe_gemm_bytes(shape: ProblemShape, p: Dict[str, int]) -> int:
    q, r, l = shape.bucket, shape.rows, shape.item_bytes
    # ciphertexts read once (int32) + one DB pass (int32 view) + int32 out
    return 4 * (q * r + r * l + q * l)


# -- the fused megakernel (kernels/fused_scan.py) ---------------------------
_FUSED_PALLAS_CHUNK_LOGS = (8, 10, 12)
_FUSED_PALLAS_DEPTHS = (2, 4)


def _fused_pallas_space(shape: ProblemShape) -> Dict[str, Tuple[int, ...]]:
    tiles = sorted({legal_tile(shape.rows, t, pow2=True)
                    for t in _DPXOR_TILES})
    logs = sorted({min(c, shape.log_rows) for c in _FUSED_PALLAS_CHUNK_LOGS})
    return {"tile_r": tuple(tiles), "chunk_log": tuple(logs),
            "depth": _FUSED_PALLAS_DEPTHS}


def _fused_pallas_legalize(shape: ProblemShape,
                           p: Dict[str, int]) -> Dict[str, int]:
    """Coupled constraints the product space can't express: one DMA tile
    must hold whole chunks (chunk_log <= log2(tile_r)) and the rotating
    buffer count never exceeds the tile count (deeper is pure waste)."""
    tr = legal_tile(shape.rows, p["tile_r"], pow2=True)
    cl = min(p["chunk_log"], shape.log_rows, tr.bit_length() - 1)
    d = max(1, min(p["depth"], shape.rows // tr))
    return {**p, "tile_r": tr, "chunk_log": cl, "depth": d}


#: Mosaic's VMEM for the in-kernel GGM expansion, in u32 words per DB row
#: of a tile and per query (ChaCha state, both children, the interleave
#: and the fold / share-conversion temporaries), queries padded to 4
#: sublanes. Calibrated against the v5e compiler's scoped-VMEM reports
#: (tests/test_tpu_compile.py compiles a case on each side of the 16 MiB
#: bound); the model over-counts by a few percent, never under.
_EXPAND_WORDS_XOR = 128
_EXPAND_WORDS_ADD = 144
_FOLD_WORDS = 256


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fused_pallas_common(shape: ProblemShape, p: Dict[str, int],
                         words_per_row: int) -> Tuple[int, int, int]:
    """(tile_r, depth, VMEM bytes both bodies hold besides their DB
    buffers): the expansion, two key-group slots (5 rows of chunk roots,
    128 lanes or one tile's chunks where more), the VMEM-resident CW
    levels and the output block."""
    q = shape.bucket
    tr = p.get("tile_r", legal_tile(shape.rows, 2048, pow2=True))
    cl = min(p.get("chunk_log", 12), tr.bit_length() - 1)
    d = p.get("depth", 2)
    q8 = _round_up(q, 8)
    key_group = 5 * q8 * max(128, tr >> cl)
    cw_levels = max(cl, 1) * q8 * 128
    out = q8 * _round_up(shape.item_bytes, 128)
    words = (_round_up(q, 4) * tr * words_per_row + 2 * key_group
             + cw_levels + out)
    return tr, d, U32_BYTES * words


def _fused_pallas_xor_footprint(shape: ProblemShape,
                                p: Dict[str, int]) -> int:
    tr, d, rest = _fused_pallas_common(shape, p, _EXPAND_WORDS_XOR)
    # d rotating u32 DB buffers [W, TR]; the masked tile's lane fold
    # keeps up to _FOLD_WORDS words per (query, record word)
    fold = _round_up(shape.bucket, 4) * shape.words * _FOLD_WORDS
    return (d * shape.words * tr + fold) * U32_BYTES + rest


def _fused_pallas_xor_bytes(shape: ProblemShape, p: Dict[str, int]) -> int:
    q, r, w = shape.bucket, shape.rows, shape.words
    cl = p.get("chunk_log", 12)
    c = max(1, r >> cl)
    # THE headline: the DB streams HBM->VMEM once per *batch* (vs once per
    # query for fused-jnp); queries ship chunk roots + clog CW levels
    key_words = c * 5 + cl * 6            # roots[4]+t per chunk, (4+2)/level
    return (r * w + q * key_words + q * w) * U32_BYTES


def _fused_pallas_add_footprint(shape: ProblemShape,
                                p: Dict[str, int]) -> int:
    tr, d, rest = _fused_pallas_common(shape, p, _EXPAND_WORDS_ADD)
    # d rotating int8 DB buffers [L, TR] + the cw_final column [Q, 1]
    q8 = _round_up(shape.bucket, 8)
    return d * shape.item_bytes * tr + rest + U32_BYTES * q8 * 128


def _fused_pallas_add_bytes(shape: ProblemShape, p: Dict[str, int]) -> int:
    q, r, l = shape.bucket, shape.rows, shape.item_bytes
    cl = p.get("chunk_log", 12)
    c = max(1, r >> cl)
    key_words = c * 5 + cl * 6 + 1        # + cw_final
    return r * l + (q * key_words + q * l) * 4


def _ggm_space(shape: ProblemShape) -> Dict[str, Tuple[int, ...]]:
    n = shape.rows                         # leaves at the widest level
    return {"tile": tuple(sorted({legal_tile(n, t) for t in _GGM_TILES}))}


def _ggm_footprint(shape: ProblemShape, p: Dict[str, int]) -> int:
    tile = p.get("tile", 65536)
    # 16 ChaCha state rows + (4 seed + 1 t) in ×2 + (8 child + 2 t) out
    return U32_BYTES * tile * (16 + 2 * 5 + 10)


MATERIALIZE_JNP = register_kernel(KernelDescriptor(
    name="xor-materialize-jnp", share_kind="xor",
    expand="materialize", scan="jnp",
    bytes_fn=lambda s, p: _xor_mat_bytes(s, p, pallas=False),
))

MATERIALIZE_PALLAS = register_kernel(KernelDescriptor(
    name="xor-materialize-pallas", share_kind="xor",
    expand="materialize", scan="pallas",
    space_fn=_xor_scan_space, footprint_fn=_xor_scan_footprint,
    bytes_fn=lambda s, p: _xor_mat_bytes(s, p, pallas=True),
))

FUSED_XOR = register_kernel(KernelDescriptor(
    name="xor-fused", share_kind="xor",
    expand="fused", scan="jnp",
    space_fn=_fused_space, footprint_fn=_fused_footprint,
    bytes_fn=_fused_bytes,
))

FUSED_PALLAS_XOR = register_kernel(KernelDescriptor(
    name="xor-fused-pallas", share_kind="xor",
    expand="fused-pallas", scan="pallas",
    space_fn=_fused_pallas_space, legalize_fn=_fused_pallas_legalize,
    footprint_fn=_fused_pallas_xor_footprint,
    bytes_fn=_fused_pallas_xor_bytes,
))

GEMM_JNP = register_kernel(KernelDescriptor(
    name="gemm-jnp", share_kind="additive",
    expand="materialize", scan="jnp",
    bytes_fn=_gemm_bytes,
))

GEMM_PALLAS = register_kernel(KernelDescriptor(
    name="gemm-pallas", share_kind="additive",
    expand="materialize", scan="pallas",
    space_fn=_gemm_space, footprint_fn=_gemm_footprint,
    bytes_fn=_gemm_bytes,
))

FUSED_PALLAS_GEMM = register_kernel(KernelDescriptor(
    name="gemm-fused-pallas", share_kind="additive",
    expand="fused-pallas", scan="pallas",
    space_fn=_fused_pallas_space, legalize_fn=_fused_pallas_legalize,
    footprint_fn=_fused_pallas_add_footprint,
    bytes_fn=_fused_pallas_add_bytes,
))

LWE_GEMM_JNP = register_kernel(KernelDescriptor(
    name="lwe-gemm-jnp", share_kind="lwe",
    expand="materialize", scan="jnp",
    bytes_fn=_lwe_gemm_bytes,
))

LWE_GEMM_PALLAS = register_kernel(KernelDescriptor(
    name="lwe-gemm-pallas", share_kind="lwe",
    expand="materialize", scan="pallas",
    space_fn=_gemm_space, footprint_fn=_lwe_gemm_footprint,
    bytes_fn=_lwe_gemm_bytes, mosaic=False,
))

GGM_EXPAND = register_kernel(KernelDescriptor(
    name="ggm-expand", share_kind="prg", serve=False,
    space_fn=_ggm_space, footprint_fn=_ggm_footprint,
))


# ---------------------------------------------------------------------------
# Plan <-> descriptor bridges
# ---------------------------------------------------------------------------

def plans_from_kernel(desc: KernelDescriptor, shape: ProblemShape, *,
                      base_plan, max_candidates: Optional[int] = None):
    """ExecutionPlan candidates of one serve descriptor for one shape.

    ``base_plan`` supplies the non-kernel axes (collective, default
    chunk_log); tunables overwrite their plan fields. Parameter names in
    descriptor spaces deliberately match ``ExecutionPlan`` field names.
    """
    if not desc.serve:
        raise ValueError(f"{desc.name} is not a serve-path kernel")
    out = []
    for params in desc.candidates(shape, max_candidates):
        out.append(replace(base_plan, expand=desc.expand, scan=desc.scan,
                           **params))
    if not out:
        # a descriptor with an empty (or fully pruned) space still offers
        # its base form — e.g. the jnp oracles have no tunables
        if desc.space_fn(shape) == {} and desc.feasible(shape, {}):
            out.append(replace(base_plan, expand=desc.expand,
                               scan=desc.scan))
    return out


def descriptor_for_plan(plan, share_kind: str) -> KernelDescriptor:
    """The registered descriptor a plan executes on (for byte models):
    the one of the share algebra with the plan's ``expand`` and ``scan``.
    The fused XOR body ignores ``scan`` (its inner fold is always the jnp
    dpxor).
    """
    for d in serve_kernels(share_kind):
        if d.expand == plan.expand and (plan.expand == "fused"
                                        or d.scan == plan.scan):
            return d
    raise KeyError(f"no registered kernel for plan {plan.name!r} "
                   f"({share_kind})")


def plan_params(plan) -> Dict[str, int]:
    """The tunable fields of a plan, as a descriptor params dict."""
    return {"tile_r": plan.tile_r, "tile_q": plan.tile_q,
            "tile_l": plan.tile_l, "chunk_log": plan.chunk_log,
            "depth": plan.depth}


def predicted_step_bytes(plan, share_kind: str, shape: ProblemShape) -> int:
    """Modeled HBM bytes one answer step moves under ``plan`` (per shard).

    The memory-roofline numerator (`analysis/roofline.py` HBM_BW divides
    it into a time bound); surfaced by ``engine.plan_report`` next to each
    bucket's chosen plan.
    """
    desc = descriptor_for_plan(plan, share_kind)
    return desc.bytes_fn(shape, plan_params(plan))
