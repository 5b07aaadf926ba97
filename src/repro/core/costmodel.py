"""Roofline cost model: compiled HLO -> {compute, memory, collective} seconds.

This is the trial evaluator on CPU-only infrastructure (DESIGN.md §2.2):
the paper measures wall-clock medians; we derive the three roofline terms
of the *compiled* step on the production mesh from
``compiled.cost_analysis()`` (FLOPs, HBM bytes) and the collective ops
parsed out of the partitioned HLO text.

Hardware model (TPU v5e, per chip): 197 TFLOP/s bf16 (98.5 f32),
819 GB/s HBM, ~50 GB/s/link ICI per mesh axis, 25 GB/s DCN (pod axis).

NOTE on normalization: XLA's post-SPMD ``cost_analysis()`` reports the
per-partition program, so FLOPs/bytes are *per chip*; the roofline terms
divide by per-chip peaks directly.  (Empirically verified in
tests/test_costmodel.py.)
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.models.layers import query_chunks

HW = {
    "flops_bf16": 197e12,
    "flops_f32": 98.5e12,
    "hbm_bw": 819e9,
    "ici_bw": 50e9,
    "dcn_bw": 25e9,
    "hbm_per_chip": 16e9,          # v5e 16 GB
    "ici_latency": 1e-6,           # per collective op fixed cost (s)
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes_out: int       # per-partition output bytes
    group_size: int
    dtype: str = ""


@dataclasses.dataclass
class CollectiveStats:
    ops: List[CollectiveOp]

    def summary(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for op in self.ops:
            d = out.setdefault(op.kind, {"count": 0, "bytes": 0.0})
            d["count"] += 1
            d["bytes"] += op.bytes_out
        return out

    def total_bytes(self) -> float:
        return sum(op.bytes_out for op in self.ops)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Parse per-partition collective ops out of (S)PMD-partitioned HLO."""
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2).lower()
        nbytes = _shape_bytes(shape_str)
        dts = _SHAPE_RE.findall(shape_str)
        dtype = dts[0][0] if dts else ""
        gs = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            gs = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                first = gl.group(1).split("}")[0].split("{")[-1]
                gs = max(1, len([x for x in first.split(",") if x.strip()]))
        ops.append(CollectiveOp(kind, nbytes, gs, dtype))
    return CollectiveStats(ops)


def collective_seconds(stats: CollectiveStats, pod_size: int = 256,
                       ici_bw: float = None, dcn_bw: float = None,
                       compute_dtype: str = "float32") -> float:
    """Ring-model time: per op, (g-1)/g x bytes / bw (x2 for all-reduce).

    Groups larger than a pod (or equal to the pod count on a multi-pod
    mesh, i.e. size<=4 here) crossing DCN use the DCN bandwidth.

    XLA-CPU's AllReducePromotion pass rewrites every small-dtype
    reduction to f32 (bf16 reductions crash the backend otherwise), so
    under bf16 compute the parsed f32 reduction payloads are halved back
    to the dtype a TPU would put on the wire (documented §7)."""
    ici = ici_bw or HW["ici_bw"]
    dcn = dcn_bw or HW["dcn_bw"]
    promoted = compute_dtype != "float32"
    t = 0.0
    for op in stats.ops:
        g = max(op.group_size, 1)
        if g == 1:
            continue
        nbytes = op.bytes_out
        if (promoted and op.dtype == "f32"
                and op.kind in ("all-reduce", "reduce-scatter")):
            nbytes *= 0.5
        bw = dcn if (g <= 4 or g > pod_size) else ici
        ring = (g - 1) / g
        factor = 2.0 * ring if op.kind == "all-reduce" else ring
        if op.kind == "collective-permute":
            factor = 1.0
        t += factor * nbytes / bw + HW["ici_latency"]
    return t


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes: float
    coll_summary: Dict[str, Dict[str, float]]
    peak_mem_bytes: Optional[float] = None

    @property
    def total_s(self) -> float:
        # terms overlap on real hardware; the roofline step time is the max
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bottleneck(self) -> str:
        vals = {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}
        return max(vals, key=vals.get)

    def as_dict(self) -> Dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "total_s": self.total_s,
            "bottleneck": self.bottleneck,
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "collective_bytes": self.collective_bytes,
            "coll_summary": self.coll_summary,
            "peak_mem_bytes": self.peak_mem_bytes,
        }


def roofline_from_dict(d: Dict) -> "Roofline":
    """Inverse of Roofline.as_dict (drops the derived total/bottleneck);
    used by the compile cache to rehydrate memoized measurements."""
    fields = {f.name for f in dataclasses.fields(Roofline)}
    return Roofline(**{k: v for k, v in d.items() if k in fields})


def analyze(compiled, compute_dtype: str = "bfloat16",
            pod_size: int = 256, flash_attention_correction: float = 0.0
            ) -> Roofline:
    """Roofline terms from a compiled executable (per-chip program)."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    peak = HW["flops_bf16"] if compute_dtype != "float32" else HW["flops_f32"]
    try:
        txt = compiled.as_text()
    except Exception:
        txt = ""
    stats = parse_collectives(txt)
    mem = None
    try:
        ma = compiled.memory_analysis()
        # peak = live arguments + temporaries (donated outputs alias args)
        mem = (getattr(ma, "argument_size_in_bytes", 0)
               + getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        pass
    mem_bytes = max(0.0, byts - flash_attention_correction)
    return Roofline(
        compute_s=flops / peak,
        memory_s=mem_bytes / HW["hbm_bw"],
        collective_s=collective_seconds(stats, pod_size=pod_size,
                                        compute_dtype=compute_dtype),
        flops_per_chip=flops,
        bytes_per_chip=byts,
        collective_bytes=stats.total_bytes(),
        coll_summary=stats.summary(),
        peak_mem_bytes=mem,
    )


# ----------------------------------------------------- flash correction
def attention_applications(cfg, shape):
    """[(count, S_q, S_kv)] softmax-attention applications per step."""
    S = shape.seq_len
    if shape.kind == "decode":
        return []                       # one-token scores are negligible
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        return [(cfg.n_layers, S, S)]
    if fam == "hybrid":
        return [(cfg.n_layers // cfg.attn_every, S, S)]
    if fam == "ssm":
        return []
    if fam == "encdec":
        S_enc = S // cfg.enc_seq_ratio
        return [(cfg.enc_layers, S_enc, S_enc),   # encoder self
                (cfg.n_layers, S, S),             # decoder self
                (cfg.n_layers, S, S_enc)]         # cross
    raise ValueError(fam)


def attention_shards(cfg, rt, data_size: int, model_size: int) -> int:
    """How many ways the (B,H,Sq,Skv) attention tensors are sharded:
    batch over the data axes always; heads over the model axis only when
    divisible (otherwise replicated — the attn_tp_fallback situation)."""
    heads_sharded = (cfg.n_heads % max(1, model_size) == 0
                     or rt.attn_tp_fallback == "batch_shard")
    return data_size * (model_size if heads_sharded else 1)


def flash_refetch_bytes(cfg, shape, rt, data_size: int,
                        model_size: int) -> float:
    """Per-chip HBM bytes the flash kernel itself moves for the S x S
    part: K/V tiles re-fetched once per Q-tile (file.buffer knob)."""
    if rt.attn_impl != "pallas":
        return 0.0
    B, H, hd = shape.global_batch, cfg.n_heads, cfg.hd
    shards = attention_shards(cfg, rt, data_size, model_size)
    kvb = 2 if rt.compute_dtype != "float32" else 4
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd + 2 bwd passes
    total = 0.0
    for count, sq, skv in attention_applications(cfg, shape):
        n_qtiles = max(1, sq // max(1, rt.attn_block_q))
        total += count * mult * n_qtiles * 2.0 * B * H * skv * hd * kvb
    return total / shards


def flash_memory_correction_bytes(cfg, shape, rt, data_size: int,
                                  model_size: int) -> float:
    """Per-chip HBM bytes REMOVED from the memory term when the Pallas
    flash kernel replaces the XLA reference attention (DESIGN.md §7.3).

    XLA materializes the (B,H,Sq,Skv) f32 score/softmax tensors in HBM
    (~4 round-trip passes for train incl. backward, 2 for prefill); the
    kernel keeps them in VMEM, at the cost of re-fetching the K/V tiles
    once per Q-tile (the spark.shuffle.file.buffer knob).  Reported as a
    separate correction, never silently folded into raw HLO numbers.
    """
    if rt.attn_impl != "pallas":
        return 0.0
    B, H, hd = shape.global_batch, cfg.n_heads, cfg.hd
    shards = attention_shards(cfg, rt, data_size, model_size)
    kvb = 2 if rt.compute_dtype != "float32" else 4
    passes = 4.0 if shape.kind == "train" else 2.0
    total = 0.0
    for count, sq, skv in attention_applications(cfg, shape):
        xla = passes * B * H * sq * skv * 4.0
        n_qtiles = max(1, sq // max(1, rt.attn_block_q))
        refetch = max(0, n_qtiles - 1) * 2.0 * B * H * skv * hd * kvb
        total += count * max(0.0, xla - refetch)
    return total / shards


def xla_score_pairs(sq: int, skv: int) -> int:
    """Query x key pairs the XLA attention (``layers.full_attention``)
    scores for one (batch, head): the staircase of its query chunks when
    the application is square (causal self-attention), else the whole
    rectangle."""
    chunks = query_chunks(sq, skv, causal=sq == skv)
    if len(chunks) == 1:
        return sq * skv
    return sum((e - s) * e for s, e in chunks)


def flash_peak_correction_bytes(cfg, shape, rt, data_size: int,
                                model_size: int) -> float:
    """Per-chip PEAK bytes removed by the flash kernel: the f32 score and
    softmax tensors the XLA attention holds over the pairs it scores
    (``xla_score_pairs``).  With remat 'none'/'dots' (dots_saveable keeps
    dot outputs) every layer's are live for the backward; with 'full'
    (or forward-only steps) only the layer in flight's are."""
    if rt.attn_impl != "pallas":
        return 0.0
    B, H = shape.global_batch, cfg.n_heads
    shards = attention_shards(cfg, rt, data_size, model_size)
    stored_all = shape.kind == "train" and rt.remat_policy in ("none",
                                                               "dots")
    # f32 bytes held per scored pair, measured on the calibrated XLA peak
    # of smollm-135m (reduced, where the scores are nearly all of the
    # peak) at 4 x 2048 on the CPU: ~3.3 (whole square) to ~4 (query
    # chunks) per layer when the backward keeps them; ~2 (forward) to
    # ~3.3-4.5 (forward + backward) for the one layer in flight
    # otherwise.  The factors take the low end: a correction that removes
    # more than the XLA program holds makes the fit check pass
    # configurations that do not fit.
    per_pair = 3.0 if stored_all else 2.0
    total = 0.0
    for count, sq, skv in attention_applications(cfg, shape):
        live = count if stored_all else min(count, 1)
        total += live * per_pair * B * H * xla_score_pairs(sq, skv) * 4.0
    return total / shards


# ------------------------------------------------- analytic memory model
# XLA-CPU "bytes accessed" proved unreliable for HBM-traffic purposes
# (unfused elementwise chains count full round-trips per op and differ
# wildly by dtype; measured 2.2x inflation for bf16 vs f32 on identical
# math).  The memory term is therefore derived from first principles —
# params / activations / attention / vocab / optimizer / KV traffic —
# which is exactly dtype- and knob-sensitive.  FLOPs and collective bytes
# stay HLO-derived (reliable).  Constants documented inline.

_ACT_RT_FWD = 8.0      # residual-stream round-trips per layer, forward
_ACT_RT_BWD = 16.0     # backward ~2x forward
_WIDE_RT_FWD = 3.0     # d_ff-wide tensors per layer, forward
_WIDE_RT_BWD = 6.0


def _layer_width(cfg) -> float:
    """Effective 'wide' dim per layer (d_ff; experts: top_k x d_ff;
    ssm: expanded inner dim)."""
    if cfg.family == "moe":
        return float(cfg.top_k * cfg.d_ff)
    if cfg.family in ("hybrid",):
        return float(cfg.ssm_expand * cfg.d_model * 2)
    if cfg.family == "ssm":
        return float(cfg.n_heads * cfg.hd * 3)
    return float(cfg.d_ff)


def analytic_memory_bytes(cfg, shape, rt, data_size: int,
                          model_size: int) -> float:
    """Per-chip HBM bytes of one step (the roofline memory term)."""
    chips = data_size * model_size
    comp_b = 4 if rt.compute_dtype == "float32" else 2
    p_b = 4 if cfg.param_dtype == "float32" else 2
    train = shape.kind == "train"
    B, S = shape.global_batch, shape.seq_len
    tokens = B * (1 if shape.kind == "decode" else S)
    L = max(1, cfg.n_layers)

    # ---- parameters: read once per forward; backward reads them again;
    # remat 'full' recomputes the forward (one more read); each extra
    # microbatch re-reads them; cast read(p_b)+write(comp_b) if casting
    n_params = cfg.param_count()
    fwd_passes = 1.0 + (1.0 if train and rt.remat_policy == "full" else 0.0)
    passes = (fwd_passes + 1.0) if train else fwd_passes
    passes *= max(1, rt.microbatches if train else 1)
    param_traffic = n_params * (p_b + comp_b) * passes / chips
    if train:
        # optimizer: read grads+params+2 moments, write params+2 moments
        state_b = 4.0 * (7.0 if cfg.optimizer == "adamw" else 3.0)
        param_traffic += n_params * state_b / chips

    # ---- activations (residual stream replicated over model axis
    # unless seq_parallel; wide tensors sharded over model)
    d = cfg.d_model
    res_shards = data_size * (model_size if rt.seq_parallel else 1)
    act_rt = _ACT_RT_FWD + (_ACT_RT_BWD if train else 0.0) \
        + (_ACT_RT_FWD if train and rt.remat_policy == "full" else 0.0)
    act = L * tokens * d * comp_b * act_rt / res_shards
    wide_rt = _WIDE_RT_FWD + (_WIDE_RT_BWD if train else 0.0) \
        + (_WIDE_RT_FWD if train and rt.remat_policy == "full" else 0.0)
    act += L * tokens * _layer_width(cfg) * comp_b * wide_rt / chips
    # remat-saved residuals are written once and read once in backward,
    # in remat_save_dtype
    if train and rt.remat_policy != "none":
        save_b = 2 if rt.remat_save_dtype == "bfloat16" else comp_b
        act += 2.0 * L * tokens * d * save_b / res_shards

    # ---- attention S x S traffic
    attn = 0.0
    shards = attention_shards(cfg, rt, data_size, model_size)
    H, hd = cfg.n_heads, cfg.hd
    for count, sq, skv in attention_applications(cfg, shape):
        if rt.attn_impl == "pallas":
            n_qtiles = max(1, sq // max(1, rt.attn_block_q))
            mult = 3.0 if train else 1.0
            attn += (count * mult * n_qtiles * 2.0
                     * B * H * skv * hd * comp_b) / shards
        else:
            passes_sq = (4.0 if train else 2.0)
            attn += count * passes_sq * B * H * sq * skv * 4.0 / shards

    # ---- vocab: logits written f32 + softmax read + backward
    V = cfg.vocab
    lg_passes = 3.0 if train else 1.0
    vocab = tokens * V * 4.0 * lg_passes / chips

    # ---- decode KV cache: read the whole live cache at stored dtype
    kv = 0.0
    if shape.kind == "decode":
        kv_b = {"int8": 1, "bfloat16": 2, "float32": 4}[rt.kv_cache_dtype]
        if cfg.family in ("dense", "vlm", "moe"):
            n_kv_layers, state = cfg.n_layers, 0
        elif cfg.family == "hybrid":
            n_kv_layers = cfg.n_layers // cfg.attn_every
            d_in = cfg.ssm_expand * cfg.d_model
            state = (cfg.n_layers * B * (d_in // cfg.ssm_head_dim)
                     * cfg.ssm_head_dim * cfg.ssm_state * 4.0)
        elif cfg.family == "ssm":
            n_kv_layers = 0
            state = cfg.n_layers * B * H * hd * hd * 4.0
        else:  # encdec: self cache + fixed cross cache
            n_kv_layers, state = cfg.n_layers * 2, 0
        kv = (n_kv_layers * 2.0 * B * S * cfg.n_kv_heads * hd * kv_b
              + 2.0 * state) / chips
        # donate=False forces a copy of the updated cache
        if not rt.donate_buffers:
            kv *= 2.0

    return param_traffic + act + attn + vocab + kv


# ------------------------------------------------------------ calibration
# XLA's cost_analysis counts a `while` body ONCE regardless of trip count
# (verified: tests/test_costmodel_calibration.py), so roofline terms for
# scanned layer stacks are recovered by compiling two small UNROLLED
# variants (1 unit and 3 units of layers) and extrapolating linearly:
#     term(U) = outside + U * per_unit.
# The unit is one scan iteration of the outermost stack (a layer; for
# hybrid/ssm families a GROUP of attn_every/slstm_every layers).
# Known residual undercounts (documented in DESIGN.md §7): inner
# chunk/time scans (Mamba2 cross-chunk state, sLSTM recurrence) remain
# body-once within a unit; their per-unit share is <1% FLOPs.

def calibration_points(cfg):
    """[(small_cfg, units), (mid_cfg, units)], true_units for ``cfg``."""
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        return ([(cfg.replace(n_layers=1), 1),
                 (cfg.replace(n_layers=3), 3)], float(cfg.n_layers))
    if fam == "encdec":
        # enc and dec stacks both scale with the unit count
        return ([(cfg.replace(n_layers=1, enc_layers=1), 1),
                 (cfg.replace(n_layers=3, enc_layers=3), 3)],
                float(cfg.n_layers))
    if fam == "hybrid":
        ae = cfg.attn_every
        # unit = one group (ae mamba blocks + shared attn); the remainder
        # mamba blocks count as rem/ae of a group (attn share is small)
        return ([(cfg.replace(n_layers=ae), 1),
                 (cfg.replace(n_layers=3 * ae), 3)],
                cfg.n_layers / ae)
    if fam == "ssm":
        se = cfg.slstm_every
        return ([(cfg.replace(n_layers=se), 1),
                 (cfg.replace(n_layers=3 * se), 3)],
                cfg.n_layers / se)
    raise ValueError(fam)


def extrapolate(v1: float, v3: float, units: float) -> float:
    """outside + units*per_unit from measurements at 1 and 3 units."""
    per_unit = max(0.0, (v3 - v1) / 2.0)
    outside = max(0.0, v1 - per_unit)
    return outside + units * per_unit


def extrapolate_roofline(r1: "Roofline", r3: "Roofline", units: float
                         ) -> "Roofline":
    ex = lambda a, b: extrapolate(a, b, units)
    coll = {}
    for kind in set(r1.coll_summary) | set(r3.coll_summary):
        a = r1.coll_summary.get(kind, {"count": 0, "bytes": 0.0})
        b = r3.coll_summary.get(kind, {"count": 0, "bytes": 0.0})
        coll[kind] = {"count": ex(a["count"], b["count"]),
                      "bytes": ex(a["bytes"], b["bytes"])}
    return Roofline(
        compute_s=ex(r1.compute_s, r3.compute_s),
        memory_s=ex(r1.memory_s, r3.memory_s),
        collective_s=ex(r1.collective_s, r3.collective_s),
        flops_per_chip=ex(r1.flops_per_chip, r3.flops_per_chip),
        bytes_per_chip=ex(r1.bytes_per_chip, r3.bytes_per_chip),
        collective_bytes=ex(r1.collective_bytes, r3.collective_bytes),
        coll_summary=coll,
        peak_mem_bytes=(ex(r1.peak_mem_bytes, r3.peak_mem_bytes)
                        if r1.peak_mem_bytes and r3.peak_mem_bytes
                        else None),
    )


def model_flops(cfg, shape, per_token_factor: float = 6.0) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) useful model FLOPs for the cell.

    train: 6ND; prefill: 2ND (forward only); decode: 2N per token.
    encdec: encoder params see only the (seq/ratio) frame tokens."""
    factor = 6.0 if shape.kind == "train" else 2.0
    B = shape.global_batch
    tokens = B * (1 if shape.kind == "decode" else shape.seq_len)
    if cfg.family == "encdec":
        enc, dec, embed = cfg.encdec_split()
        enc_tokens = (B * (shape.seq_len // cfg.enc_seq_ratio)
                      if shape.kind != "decode" else 0)
        return factor * (enc * enc_tokens + (dec + embed) * tokens)
    return factor * cfg.active_param_count() * tokens
