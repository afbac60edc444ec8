"""Three-term roofline from a compiled (dry-run) executable.

    compute_term    = HLO_FLOPs_per_device / PEAK_FLOPS_BF16
    memory_term     = HLO_bytes_per_device / HBM_BW
    collective_term = collective_bytes_per_device / ICI_LINK_BW

``compiled.cost_analysis()`` supplies flops & bytes of the *partitioned*
(per-device) module.  Collective bytes are NOT in cost_analysis: we parse the
optimized HLO text and sum the operand bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute instruction
(methodology note: operand bytes ~ data injected into the interconnect by
each device; ring-algorithm constant factors are not modeled, link count per
collective is taken as 1 -- uniform across all cells so comparisons and
bottleneck attribution stand).

``model_flops`` computes the analytic useful-FLOPs (6*N*D train / 2*N*D
inference, + attention quadratic terms, MoE-active-param aware), giving the
MODEL_FLOPS / HLO_FLOPs efficiency ratio that catches remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, Optional

from repro.configs.base import ModelConfig, ShapeConfig
from repro.roofline import hw

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# shape tokens like f32[256,1024]{1,0} or bf16[8,128]
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*((?:\(?[a-z][a-z0-9]*\[[0-9,]*\]"
    r"[^ ]*\s*,?\s*)+\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\s*\(",
)
# replica_groups={{0,1},{2,3}} or iota form replica_groups=[4,2]<=[8]
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in hw.DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * hw.DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return 1


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Per-device *operand* bytes per collective kind, from optimized HLO.

    The HLO text types the RESULT, not the operands, so operand bytes are
    reconstructed per op semantics with the replica-group size g:
      all-gather: operand = result / g     reduce-scatter: operand = result*g
      all-reduce / all-to-all / collective-permute: operand = result.
    Async pairs (-start/-done) are counted once at -start.
    """
    by_kind: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        kind = m.group(2)
        suffix = m.group(3) or ""
        if suffix == "-done":
            continue
        result_bytes = _shape_bytes(m.group(1))
        g = _group_size(line)
        if kind == "all-gather":
            nbytes = result_bytes / max(g, 1)
        elif kind == "reduce-scatter":
            nbytes = result_bytes * max(g, 1)
        else:
            nbytes = result_bytes
        by_kind[kind] += nbytes
        counts[kind] += 1
    total = sum(by_kind.values())
    return {
        "total_bytes": total,
        "bytes_by_kind": by_kind,
        "count_by_kind": counts,
    }


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    hlo_flops: float  # per device
    hlo_bytes: float  # per device
    collective_bytes: float  # per device
    compute_term_s: float
    memory_term_s: float
    collective_term_s: float
    bottleneck: str
    model_flops: float  # global useful flops
    useful_ratio: float  # model_flops / (hlo_flops * n_chips)
    memory_per_device: Dict[str, float]
    collectives: Dict[str, Any]
    extra: Dict[str, Any]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)

    @property
    def step_time_bound_s(self) -> float:
        return max(
            self.compute_term_s, self.memory_term_s, self.collective_term_s
        )

    def roofline_fraction(self) -> float:
        """max(useful-compute, minimal-traffic) time / bound step time.

        The minimal-traffic floor matters for decode shapes, which are
        bandwidth-bound by construction (every parameter + the KV cache must
        cross HBM once per token) -- without it a perfect decode step would
        still score ~0.
        """
        useful_t = (self.model_flops / self.n_chips) / hw.PEAK_FLOPS_BF16
        min_bytes = self.extra.get("model_bytes", 0.0)
        traffic_t = (min_bytes / self.n_chips) / hw.HBM_BW
        bound = self.step_time_bound_s
        return max(useful_t, traffic_t) / bound if bound > 0 else 0.0


_KERNEL_RE = re.compile(r"jit\((\w+)\)\)*/pallas_call")


def pallas_kernel_counts(hlo_text: str) -> Dict[str, int]:
    """Pallas kernels in a compiled TPU program: its ``tpu_custom_call``
    instructions grouped by the jitted kernel wrapper that emitted each one
    (the innermost ``jit(<name>)`` before ``/pallas_call`` in the op
    metadata, also under a transform: ``jvp(jit(<name>))/pallas_call``).  Counts HLO occurrences: a kernel inside a scanned layer
    body counts once, not once per layer."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        names = _KERNEL_RE.findall(line)
        name = names[-1] if names else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def analyze(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    n_chips: int,
    model_flops: float,
    hlo_text: Optional[str] = None,
    extra: Optional[Dict[str, Any]] = None,
    corrections: Optional[Dict[str, Dict[str, float]]] = None,
) -> RooflineReport:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    # Scan-body corrections (global quantities -> per-device).
    corr_flops = sum(c["flops"] for c in (corrections or {}).values())
    corr_bytes = sum(c["bytes"] for c in (corrections or {}).values())
    flops = flops_raw + corr_flops / n_chips
    nbytes = bytes_raw + corr_bytes / n_chips
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_stats(text)
    cbytes = float(coll["total_bytes"])

    compute_t = flops / hw.PEAK_FLOPS_BF16
    memory_t = nbytes / hw.HBM_BW
    collective_t = cbytes / hw.ICI_LINK_BW
    terms = {
        "compute": compute_t, "memory": memory_t, "collective": collective_t
    }
    bottleneck = max(terms, key=terms.get)

    mem: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            if hasattr(ma, attr):
                mem[attr] = float(getattr(ma, attr))
    except Exception as e:  # noqa: BLE001 -- backend-dependent
        mem["error"] = 0.0

    full_extra = dict(extra or {})
    full_extra["hlo_flops_raw"] = flops_raw
    full_extra["hlo_bytes_raw"] = bytes_raw
    full_extra["scan_corrections"] = corrections or {}
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        n_chips=n_chips,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        collective_bytes=cbytes,
        compute_term_s=compute_t,
        memory_term_s=memory_t,
        collective_term_s=collective_t,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(
            model_flops / (flops * n_chips) if flops > 0 else 0.0
        ),
        memory_per_device=mem,
        collectives=coll,
        extra=full_extra,
    )


# ---------------------------------------------------------------------------
# Scan-body corrections
# ---------------------------------------------------------------------------
#
# XLA's HloCostAnalysis counts while-loop bodies ONCE (verified empirically:
# a 10-step scanned matmul reports 1/10 the flops of its unrolled twin).  The
# dry-run therefore lowers with scan_layers=False (layers python-unrolled, so
# the dominant per-layer GEMMs are counted exactly) and adds ANALYTIC
# corrections for the remaining inner loops -- chunked-attention blocks,
# chunked-xent blocks, SSD chunks -- each correction = analytic_flops x
# (1 - 1/n_iterations), itemized in the artifact for transparency.

EXACT_ATTN_MAX_ELEMS = 2048 * 2048  # mirror of models/attention.py auto rule


def _attn_is_chunked(cfg: ModelConfig, sq: int, sk: int) -> bool:
    if cfg.attn_impl == "exact":
        return False
    if cfg.attn_impl in ("chunked", "pallas"):
        return True
    return not (sq == 1 or sq * sk <= EXACT_ATTN_MAX_ELEMS)


def scan_corrections(
    cfg: ModelConfig, shape: ShapeConfig
) -> Dict[str, Dict[str, float]]:
    """{loop_family: {flops, bytes, n_iters}} global-quantity corrections."""
    b, s = shape.global_batch, shape.seq_len
    kind = shape.kind
    out: Dict[str, Dict[str, float]] = {}
    train_mult = 4.0 if (kind == "train" and cfg.remat == "block") else (
        3.0 if kind == "train" else 1.0
    )
    layers = cfg.n_layers + (cfg.n_enc_layers if cfg.family == "audio" else 0)

    # -- chunked self-attention blocks --
    if cfg.n_heads and kind != "decode" and _attn_is_chunked(cfg, s, s):
        nq = max(s // cfg.attn_chunk_q, 1)
        nk = max(s // cfg.attn_chunk_kv, 1)
        n_iter = nq * nk
        qdim = cfg.q_dim
        eff_k = min(s, cfg.attn_window) if cfg.attn_window else s
        causal_frac = 0.5 if not cfg.attn_window else 1.0
        flops = 4.0 * b * s * eff_k * qdim * causal_frac * cfg.n_layers
        flops *= train_mult
        kv_bytes = (
            cfg.n_layers * b
            * (nq * s * cfg.kv_dim * 2 + s * qdim * 2) * 2.0
        )
        out["attn_chunks"] = {
            "flops": flops * (1 - 1 / n_iter),
            "bytes": kv_bytes * (1 - 1 / n_iter),
            "n_iters": float(n_iter),
        }

    # -- whisper cross-attention (decoder q x 1500 enc frames) --
    if cfg.family == "audio" and kind != "decode" and _attn_is_chunked(
        cfg, s, cfg.enc_frames
    ):
        n_iter = max(s // cfg.attn_chunk_q, 1) * max(
            cfg.enc_frames // cfg.attn_chunk_kv, 1
        )
        flops = 4.0 * b * s * cfg.enc_frames * cfg.q_dim * cfg.n_layers
        flops *= train_mult
        out["cross_attn_chunks"] = {
            "flops": flops * (1 - 1 / max(n_iter, 1)),
            "bytes": 0.0,
            "n_iters": float(max(n_iter, 1)),
        }

    # -- chunked cross-entropy (train only; chunked over sequence) --
    if kind == "train":
        tokens = b * s
        n_iter = max(s // cfg.loss_chunk, 1)
        flops = 6.0 * tokens * cfg.d_model * cfg.vocab_size
        lm_head_bytes = n_iter * cfg.d_model * cfg.vocab_size * 4.0
        out["loss_chunks"] = {
            "flops": flops * (1 - 1 / n_iter),
            "bytes": lm_head_bytes * (1 - 1 / n_iter),
            "n_iters": float(n_iter),
        }

    # -- SSD chunk scan (ssm / hybrid; decode is recurrent, loop-free) --
    if cfg.ssm_state and kind != "decode":
        q = cfg.ssm_chunk
        n_iter = max(s // q, 1)
        d_inner = cfg.ssm_expand * cfg.d_model
        h = max(d_inner // cfg.ssm_head_dim, 1)
        p = cfg.ssm_head_dim
        n = cfg.ssm_state
        flops_fwd = (
            2.0 * b * s * (q * (h * p + n) + 3.0 * h * p * n) * cfg.n_layers
        )
        flops = flops_fwd * train_mult
        out["ssd_chunks"] = {
            "flops": flops * (1 - 1 / n_iter),
            "bytes": 0.0,
            "n_iters": float(n_iter),
        }
    return out


# ---------------------------------------------------------------------------
# Analytic useful FLOPs
# ---------------------------------------------------------------------------


def active_params(cfg: ModelConfig, total_params: int) -> float:
    """Active parameters per token (MoE-aware)."""
    if cfg.family != "moe" or not cfg.n_experts:
        return float(total_params)
    per_expert = 3 * cfg.d_model * cfg.d_ff  # swiglu expert
    routed = cfg.n_layers * cfg.n_experts * per_expert
    active_routed = cfg.n_layers * cfg.moe_top_k * per_expert
    return float(total_params - routed + active_routed)


def model_bytes(
    cfg: ModelConfig,
    shape: ShapeConfig,
    total_params: int,
) -> float:
    """Analytic minimal global HBM traffic per step (bf16 weights).

    train:   read params + write grads + rewrite params (master fp32-ish);
    prefill: read params once + write the KV cache;
    decode:  read params + read the whole KV/SSM cache (the decode wall).
    """
    b, s = shape.global_batch, shape.seq_len
    layers = cfg.n_layers + (cfg.n_enc_layers if cfg.family == "audio" else 0)
    kv_cache = 2.0 * layers * b * s * cfg.kv_dim * 2.0 if cfg.n_heads else 0.0
    if cfg.attn_window:
        kv_cache = (
            2.0 * layers * b * min(s, cfg.attn_window) * cfg.kv_dim * 2.0
        )
    if cfg.ssm_state:
        d_inner = cfg.ssm_expand * cfg.d_model
        kv_cache += (
            4.0 * cfg.n_layers * b
            * (d_inner // cfg.ssm_head_dim) * cfg.ssm_head_dim
            * cfg.ssm_state
        )
    if shape.kind == "train":
        return 3.0 * total_params * 4.0
    if shape.kind == "prefill":
        return total_params * 2.0 + kv_cache
    return total_params * 2.0 + kv_cache


def model_flops(
    cfg: ModelConfig,
    shape: ShapeConfig,
    total_params: int,
) -> float:
    """Useful FLOPs per step (PaLM-style accounting, causal-halved attn)."""
    n_act = active_params(cfg, total_params)
    b, s = shape.global_batch, shape.seq_len
    d_tokens = b * s
    attn_q = cfg.q_dim if cfg.n_heads else 0
    layers = cfg.n_layers + cfg.n_enc_layers
    if shape.kind == "train":
        base = 6.0 * n_act * d_tokens
        attn = 6.0 * layers * b * s * s * attn_q * 0.5 * 2  # qk+pv,fwd+bwd/2
        return base + attn
    if shape.kind == "prefill":
        base = 2.0 * n_act * d_tokens
        attn = 2.0 * layers * b * s * s * attn_q * 0.5 * 2 / 3.0
        return base + attn
    # decode: one token per sequence against an s-long cache
    base = 2.0 * n_act * b
    attn = 4.0 * layers * b * s * attn_q
    if cfg.attn_window:
        attn = 4.0 * layers * b * min(s, cfg.attn_window) * attn_q
    return base + attn
