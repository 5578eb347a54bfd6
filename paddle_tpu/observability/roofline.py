"""Serving roofline ledger (ISSUE 12): per-phase FLOPs *and* bytes.

``flops.py`` answers "how close to peak compute" — the right question
for training, where every matmul is large. Serving is different: decode
at continuous-batching sizes streams the whole weight set plus every
cached KV position per emitted token, so it pins HBM long before the
MXU, and MFU alone cannot say whether the decode tick is at hardware
speed (Williams et al., "Roofline: An Insightful Visual Performance
Model", CACM 2009). This module pairs the peak-FLOPs table with a peak
HBM-bandwidth table and carries analytic per-phase FLOPs and bytes
models, so every serving phase gets THREE numbers:

  * ``serving_mfu{phase}``              — FLOPs/s vs the chip's bf16 peak
  * ``serving_mbu{phase}``              — bytes/s vs the chip's HBM peak
  * ``serving_arith_intensity{phase}``  — FLOPs/byte, placing the phase
    left (bandwidth-bound) or right (compute-bound) of the machine
    balance point

Phases are the engine tick's anatomy: ``prefill`` (admission + chunked
prefill forwards), ``decode`` (the fused one-token tick), ``spec_draft``
(draft-model feeds), ``spec_verify`` (the batched (slots, k+1) target
chunk). The engine accumulates per-phase seconds / tokens / weight
passes / KV-read positions and folds them through
:func:`record_serving_throughput` — the single choke point, mirroring
``flops.record_throughput`` — at every gauge sweep.

Conventions shared with ``flops.py``: import-light (nothing here may
import jax or the ``paddle_tpu`` root — a process that holds no chip
must be able to reason about rooflines off-device), and an
unknown chip yields peak 0.0 → every utilisation gauge reads 0.0 =
"undefined", never a fabricated number. ``PT_ROOFLINE_KIND`` overrides
the detected device kind (e.g. ``PT_ROOFLINE_KIND="TPU v5e"``) for
what-if analysis and for testing the TPU arithmetic on CPU.

Bytes model scope: weights (every resident weight streamed once per
jitted forward — all experts for MoE, the batch routes across them),
KV reads (2 × kv_heads × head_dim per layer per attended position —
GQA grouping shrinks this by heads/kv_heads; the engine counts decode
positions block-rounded because the paged kernel reads whole blocks),
KV writes (one position per token), and f32 logits. Activations are
deliberately excluded — they are layer-local and VMEM-resident at
serving batch sizes.
"""
from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass, asdict

from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.observability.flops import (PEAK_BF16, chip_peak,
                                            chip_peak_flops)

__all__ = ["PEAK_HBM_BPS", "chip_peak_hbm_bw", "resolve_serving_peaks",
           "ModelGeometry", "weight_bytes", "kv_bytes_per_position",
           "phase_flops", "phase_bytes", "arith_intensity",
           "latent_read_bytes", "grouped_products_flops",
           "grouped_products_bytes",
           "roofline_verdict", "record_serving_throughput",
           "serving_roofline_report", "reset_serving_roofline"]

# Peak HBM bandwidth per chip, bytes/sec — the denominator of MBU, keyed
# exactly like PEAK_BF16 so the two tables can never disagree about what
# a "chip" is. (v5e 819 GB/s, v5p 2765 GB/s, v4 1228 GB/s, v6e 1640 GB/s.)
PEAK_HBM_BPS = {
    "TPU v5 lite": 819e9,    # v5e
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v6": 1640e9,
}

assert set(PEAK_HBM_BPS) == set(PEAK_BF16), \
    "PEAK_HBM_BPS and PEAK_BF16 must cover the same chips"


def chip_peak_hbm_bw(dev=None, kind: str = None) -> float:
    """Peak HBM bytes/sec for a jax device (or an explicit
    ``device_kind`` string). Same convention as ``chip_peak_flops``:
    an unknown TPU kind raises, anything that is not known to be a TPU
    returns 0.0 — callers treat 0 peak as "MBU undefined"."""
    return chip_peak(PEAK_HBM_BPS, dev, kind)


def resolve_serving_peaks(dev=None) -> tuple:
    """(peak_flops, peak_hbm_bps) for the serving roofline.
    ``PT_ROOFLINE_KIND`` (a device-kind string, e.g. ``TPU v5e``)
    overrides the detected device — what-if analysis, and the only way
    to exercise the TPU arithmetic in a CPU test without fabricating
    utilisation by default."""
    kind = os.environ.get("PT_ROOFLINE_KIND")
    if kind:
        return chip_peak_flops(kind=kind), chip_peak_hbm_bw(kind=kind)
    return chip_peak_flops(dev), chip_peak_hbm_bw(dev)


@dataclass(frozen=True)
class ModelGeometry:
    """The shape facts the FLOPs/bytes models need — duck-typed off any
    of the repo's LLM configs via :meth:`from_config`, never a live
    model (so the roofline stays importable without jax)."""
    num_layers: int               # layer applications a token (x passes)
    hidden: int
    intermediate: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    dtype_bytes: int = 2          # bf16 weights and KV
    num_experts: int = 0          # routed experts (0 = dense MLP)
    experts_per_tok: int = 0
    # quantized serving (ISSUE 17) — actual storage dtypes, so an int8
    # pool or weight-only model is not billed at bf16 (which would
    # double its bytes and overstate MBU). 0 = inherit dtype_bytes.
    kv_dtype_bytes: int = 0       # bytes per cached KV element
    kv_scale_bytes: int = 0       # extra bytes per (position, kv-head)
    weight_dtype_bytes: float = 0.0   # 1.0 int8, 0.5 packed int4
    # context-parallel serving (ISSUE 18) — cp>1 means every decode
    # token pays a cross-shard partial merge (psum of the online-softmax
    # (o, m, l) triple per layer); billed as extra bytes so
    # serving_mbu{decode} stays honest about the per-step gather cost.
    cp: int = 1
    # layers that carry a recurrent state and keep no K/V (the gated delta
    # rule of models/olmo_hybrid.py): ``linear_layers`` of ``num_layers``,
    # each with ``linear_heads`` states of linear_key_dim x linear_value_dim
    # float32 and the last ``linear_conv - 1`` inputs of its convolution
    linear_layers: int = 0
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 0
    # latent attention (MLA, models/kimi_k2.py): every layer caches ONE
    # row of latent_rank + latent_rope values a position, read by all
    # heads; q through a rank of q_rank, heads of nope_dim + latent_rope
    # (keys) and v_dim (values) expanded from the latent
    latent_rank: int = 0
    latent_rope: int = 0
    q_rank: int = 0
    nope_dim: int = 0
    v_dim: int = 0
    # expert layers in full: ``dense_layers`` leading layers keep a dense
    # MLP of ``dense_intermediate``; every expert layer adds
    # ``shared_experts`` experts all tokens take; ``held_experts`` of the
    # ``num_experts`` routed ones are resident here (0: all of them), one
    # chip's share of a wide expert-parallel deployment
    dense_layers: int = 0
    dense_intermediate: int = 0
    shared_experts: int = 0
    held_experts: int = 0

    @classmethod
    def from_config(cls, cfg, dtype_bytes: int = 2) -> "ModelGeometry":
        h = int(cfg.hidden_size)
        nh = int(cfg.num_attention_heads)
        experts = int(getattr(cfg, "num_experts", 0)
                      or getattr(cfg, "num_local_experts", 0)
                      or getattr(cfg, "n_routed_experts", 0) or 0)
        per_tok = int(getattr(cfg, "num_experts_per_tok", 0)
                      or getattr(cfg, "experts_per_tok", 0) or 0)
        inter = int(getattr(cfg, "moe_intermediate_size", 0)
                    or cfg.intermediate_size)
        # a looped model applies its layers ``total_ut_steps`` times a
        # token: its weights stream that often and every pass keeps K/V
        passes = int(getattr(cfg, "total_ut_steps", 1))
        kinds = tuple(getattr(cfg, "layer_types", None)
                      or ())[:int(cfg.num_hidden_layers)]
        linear = {}
        if "linear_attention" in kinds:
            linear = dict(linear_layers=kinds.count("linear_attention"),
                          linear_heads=int(cfg.linear_num_key_heads),
                          linear_key_dim=int(cfg.linear_key_head_dim),
                          linear_value_dim=int(cfg.linear_value_head_dim),
                          linear_conv=int(cfg.linear_conv_kernel_dim))
        if "latent_attention" in kinds:
            linear = dict(
                latent_rank=int(cfg.kv_lora_rank),
                latent_rope=int(cfg.qk_rope_head_dim),
                q_rank=int(cfg.q_lora_rank),
                nope_dim=int(cfg.qk_nope_head_dim),
                v_dim=int(cfg.v_head_dim),
                dense_layers=int(cfg.first_k_dense_replace),
                dense_intermediate=int(cfg.intermediate_size),
                shared_experts=int(cfg.n_shared_experts),
                held_experts=len(getattr(cfg, "held_experts", None) or ()))
        return cls(num_layers=int(cfg.num_hidden_layers) * passes, hidden=h,
                   intermediate=inter, vocab=int(cfg.vocab_size), heads=nh,
                   kv_heads=int(getattr(cfg, "num_key_value_heads", nh)),
                   head_dim=h // nh, dtype_bytes=int(dtype_bytes),
                   num_experts=experts, experts_per_tok=per_tok, **linear)

    # ---- derived counts -------------------------------------------------
    @property
    def attn_params_per_layer(self) -> int:
        """Fused qkv + output projection; for latent attention the two
        down-projections, the two expansions and the output projection."""
        if self.latent_rank:
            qk = self.nope_dim + self.latent_rope
            return (self.hidden * (self.q_rank + self.latent_rank
                                   + self.latent_rope)
                    + self.heads * (self.q_rank * qk + self.latent_rank
                                    * (self.nope_dim + self.v_dim)
                                    + self.v_dim * self.hidden))
        return (self.hidden * (self.heads + 2 * self.kv_heads)
                * self.head_dim + self.heads * self.head_dim * self.hidden)

    @property
    def linear_params_per_layer(self) -> int:
        """A linear layer's mixer: q, k, v, the gate z, beta and the decay,
        the depthwise convolution, the output projection."""
        h, dk, dv = self.linear_heads, self.linear_key_dim, \
            self.linear_value_dim
        chans = h * (2 * dk + dv)
        return (self.hidden * (chans + h * dv + 2 * h)
                + self.linear_conv * chans + h * dv * self.hidden)

    @property
    def mixer_params(self) -> int:
        """Every layer's token mixer: attention where the layer keeps
        K/V, the linear mixer where it carries a state."""
        attn = ((self.num_layers - self.linear_layers)
                * self.attn_params_per_layer)
        if not self.linear_layers:
            return attn
        return attn + self.linear_layers * self.linear_params_per_layer

    @property
    def mlp_params_per_expert(self) -> int:
        """gate + up + down projections of one (dense or expert) MLP."""
        return 3 * self.hidden * self.intermediate

    def _mlp_params(self, routed) -> float:
        """Every layer's MLP with ``routed`` routed experts an expert
        layer: the leading dense layers, and the shared experts beside."""
        return ((self.num_layers - self.dense_layers)
                * (routed + self.shared_experts) * self.mlp_params_per_expert
                + self.dense_layers * 3 * self.hidden
                * self.dense_intermediate)

    @property
    def activated_params(self) -> int:
        """Weight parameters ONE token's forward multiplies against:
        attention + experts_per_tok MLPs (all of the dense MLP) + head.
        With ``held_experts`` the share of its experts_per_tok a uniform
        router sends here (a fraction of an expert a token)."""
        e = self.experts_per_tok if self.num_experts else 1
        if self.held_experts:
            e = e * self.held_experts / self.num_experts
        return (self.mixer_params + self._mlp_params(e)
                + self.hidden * self.vocab)

    @property
    def resident_params(self) -> int:
        """Weight parameters a batched forward streams from HBM: every
        expert is resident (the batch routes across all of them)."""
        e = (self.held_experts or self.num_experts) if self.num_experts else 1
        return (self.mixer_params + self._mlp_params(e)
                + self.hidden * self.vocab)


def weight_bytes(geom: ModelGeometry) -> float:
    """Bytes of weights one jitted forward reads from HBM (honouring
    weight-only quantization when ``weight_dtype_bytes`` is set)."""
    return float(geom.resident_params) * (geom.weight_dtype_bytes
                                          or geom.dtype_bytes)


def kv_bytes_per_position(geom: ModelGeometry) -> float:
    """K + V bytes of ONE cached position across all layers; GQA head
    grouping makes this kv_heads/heads of the MHA figure. An int8 pool
    stores head_dim codes plus a per-(position, kv-head) scale."""
    if geom.latent_rank:
        return latent_read_bytes(geom, 1.0)
    per_head = (geom.head_dim * (geom.kv_dtype_bytes or geom.dtype_bytes)
                + geom.kv_scale_bytes)
    return float((geom.num_layers - geom.linear_layers) * 2 * geom.kv_heads
                 * per_head)


def latent_read_bytes(geom: ModelGeometry, positions: float) -> float:
    """Bytes a latent decode read moves for ``positions`` cached positions
    over all layers: the model's ``latent_rank + latent_rope`` values a row,
    ONCE for all heads and for both uses (key and value), whatever the pool
    pads a row to."""
    return float(positions * geom.num_layers
                 * (geom.latent_rank + geom.latent_rope) * geom.dtype_bytes)


def grouped_products_flops(geom: ModelGeometry, routed_pairs: float) -> float:
    """FLOPs of the grouped products over ``routed_pairs`` (token, expert)
    pairs: gate, up and down of one expert a pair, 2 a multiply-add."""
    return 2.0 * routed_pairs * geom.mlp_params_per_expert


def grouped_products_bytes(geom: ModelGeometry, experts_hit: float) -> float:
    """Weight bytes the grouped products stream for ``experts_hit`` experts
    that got at least one token (summed over layers): an expert's three
    matrices once each."""
    return (experts_hit * geom.mlp_params_per_expert
            * (geom.weight_dtype_bytes or geom.dtype_bytes))


def state_bytes_per_slot(geom: ModelGeometry) -> float:
    """Bytes of recurrent state ONE sequence holds over all linear
    layers (float32 ``S``, the conv inputs in the weights' dtype): what a
    decode token reads and writes whatever its context, and a prefill
    call once a row. 0 for a model whose every layer keeps K/V."""
    h, dk, dv = geom.linear_heads, geom.linear_key_dim, geom.linear_value_dim
    conv = max(geom.linear_conv - 1, 0) * h * (2 * dk + dv)
    return float(geom.linear_layers * (h * dk * dv * 4
                                       + conv * geom.dtype_bytes))


def phase_flops(geom: ModelGeometry, tokens: float,
                kv_read_positions: float) -> float:
    """Forward FLOPs of a phase that computed ``tokens`` token positions
    attending ``kv_read_positions`` (query, cached-position) pairs in
    total: 2 × activated params per token (matmuls), plus the qk^T and
    p·v terms — 4 × heads × head_dim FLOPs per attended pair per layer
    (2 mult-adds). The attention term rides the PAIR count, so callers
    describe causal prefill (Σ ctx per query) and single-query decode
    (whole table per token) with the same argument."""
    matmul = 2.0 * geom.activated_params * tokens
    attn = 4.0 * geom.heads * geom.head_dim * kv_read_positions
    if geom.latent_rank:
        # the absorbed form: a head's query against the whole row, its
        # probabilities against the row's latent part, in every layer (the
        # tick's form; a prefill call runs the expanded one since PR 42,
        # 3.4 times fewer: this gauge does not know the phase)
        attn = (2.0 * geom.num_layers * geom.heads * kv_read_positions
                * (2 * geom.latent_rank + geom.latent_rope))
    if not geom.linear_layers:
        return matmul + attn
    # the delta rule of the linear layers: k^T S, the rank-one write and
    # S^T q, 2 flops a multiply-add, a head and token
    rule = (6.0 * geom.linear_layers * geom.linear_heads
            * geom.linear_key_dim * geom.linear_value_dim * tokens)
    return matmul + attn + rule


def phase_bytes(geom: ModelGeometry, *, tokens: float, weight_passes: float,
                kv_read_positions: float) -> float:
    """HBM bytes of a phase: weights once per jitted forward, KV reads
    per attended (query, position) pair, one KV write per computed
    token, and the f32 logits row per token."""
    w = weight_passes * weight_bytes(geom)
    kv_r = kv_read_positions * kv_bytes_per_position(geom)
    kv_w = tokens * kv_bytes_per_position(geom)
    logits = tokens * geom.vocab * 4.0
    total = w + kv_r + kv_w + logits
    if geom.cp > 1:
        # cross-shard partial merge per computed token: each member
        # psums an f32 (o [H, D], m [H], l [H]) triple per layer —
        # 2·(cp-1)/cp of it crosses the interconnect per member
        triple = geom.num_layers * geom.heads * (geom.head_dim + 2) * 4.0
        total += tokens * triple * 2.0 * (geom.cp - 1) / geom.cp
    return total


def arith_intensity(flops: float, nbytes: float) -> float:
    """FLOPs per HBM byte — the roofline x-axis."""
    return flops / nbytes if nbytes else 0.0


def roofline_verdict(intensity: float, peak_flops: float,
                     peak_hbm_bps: float) -> str:
    """Which roof the phase sits under: intensity below the machine
    balance (peak_flops / peak_hbm) means the bandwidth roof caps it."""
    if not peak_flops or not peak_hbm_bps:
        return "undefined"
    return ("compute-bound" if intensity >= peak_flops / peak_hbm_bps
            else "bandwidth-bound")


_MFU = METRICS.gauge(
    "serving_mfu",
    "per-phase model FLOPs utilisation vs the chip bf16 peak "
    "(0.0 = undefined off-TPU)", labelnames=("phase",))
_MBU = METRICS.gauge(
    "serving_mbu",
    "per-phase model bandwidth utilisation vs the chip HBM peak "
    "(0.0 = undefined off-TPU)", labelnames=("phase",))
_AI = METRICS.gauge(
    "serving_arith_intensity",
    "per-phase arithmetic intensity, FLOPs per HBM byte",
    labelnames=("phase",))

# last full report per phase, served verbatim at /roofline
_REPORTS: dict = {}
_REPORTS_LOCK = threading.Lock()


@functools.lru_cache(maxsize=None)
def _geometry_fields(geom: ModelGeometry) -> dict:
    """``asdict(geom)``, taken once a geometry: every tick's gauge sweep
    reports it, and a field the class gains is then no cost a tick."""
    return asdict(geom)


def record_serving_throughput(phase: str, *, seconds: float, tokens: float,
                              weight_passes: float, kv_read_positions: float,
                              geom: ModelGeometry, peak_flops: float = 0.0,
                              peak_hbm_bps: float = 0.0) -> dict:
    """Single choke point for serving utilisation: fold one phase's
    cumulative (seconds, tokens, weight passes, KV-read positions)
    through the analytic models, set the three per-phase gauges, stash
    the full report for ``/roofline``, and return it. Unknown peaks
    (CPU, mock backends) keep MFU/MBU at 0.0 — undefined, never
    fabricated — while intensity and the byte/FLOP tallies stay real."""
    if seconds <= 0.0 or tokens <= 0:
        return {}
    fl = phase_flops(geom, tokens, kv_read_positions)
    by = phase_bytes(geom, tokens=tokens, weight_passes=weight_passes,
                     kv_read_positions=kv_read_positions)
    if phase == "decode" and geom.linear_layers:
        # a decode token reads and writes its sequence's recurrent state
        # (a prefill call does once a row: its weight pass dwarfs that)
        by += 2.0 * state_bytes_per_slot(geom) * tokens
    ai = arith_intensity(fl, by)
    mfu_v = fl / seconds / peak_flops if peak_flops else 0.0
    mbu_v = by / seconds / peak_hbm_bps if peak_hbm_bps else 0.0
    report = {
        "phase": phase, "seconds": seconds, "tokens": tokens,
        "weight_passes": weight_passes,
        "kv_read_positions": kv_read_positions,
        "flops": fl, "bytes": by,
        "flops_per_sec": fl / seconds, "bytes_per_sec": by / seconds,
        "arith_intensity": ai, "mfu": mfu_v, "mbu": mbu_v,
        "bound": roofline_verdict(ai, peak_flops, peak_hbm_bps),
        "geometry": dict(_geometry_fields(geom)),
    }
    _MFU.set(mfu_v, phase=phase)
    _MBU.set(mbu_v, phase=phase)
    _AI.set(ai, phase=phase)
    with _REPORTS_LOCK:
        _REPORTS[phase] = report
        _REPORTS["_machine"] = {
            "peak_flops": peak_flops, "peak_hbm_bps": peak_hbm_bps,
            "balance_flops_per_byte": (peak_flops / peak_hbm_bps
                                       if peak_hbm_bps else 0.0),
        }
    return report


def serving_tick_anatomy() -> dict:
    """Overlap-aware tick anatomy (ISSUE 20): cumulative wall-seconds
    per tick phase from the breakdown histogram, with host time split
    into *exposed* (the breakdown's ``host`` remainder — device idle
    while the host works) and *hidden* (host work done under an
    in-flight async dispatch, ``serving_tick_host_hidden_seconds``;
    zero for synchronous engines). ``overlap_fraction`` is the share of
    total host work the pipeline hid."""
    def _hist_sum(name, **labels):
        m = METRICS.get(name)
        if m is None:
            return 0.0
        try:
            return float(m.value(**labels)["sum"])
        except (KeyError, TypeError):
            return 0.0

    phases = {p: _hist_sum("serving_tick_breakdown_seconds", phase=p)
              for p in ("prefill", "draft", "verify", "sample", "host")}
    hidden = _hist_sum("serving_tick_host_hidden_seconds")
    exposed = phases["host"]
    host_total = exposed + hidden
    return {
        "ticks_seconds": _hist_sum("serving_tick_seconds"),
        "phases_seconds": phases,
        "host_exposed_seconds": exposed,
        "host_hidden_seconds": hidden,
        "overlap_fraction": hidden / host_total if host_total else 0.0,
    }


def serving_roofline_report() -> dict:
    """The ``/roofline`` document: machine roofs + the last per-phase
    reports the choke point recorded + the overlap-aware tick anatomy."""
    with _REPORTS_LOCK:
        machine = _REPORTS.get("_machine", {
            "peak_flops": 0.0, "peak_hbm_bps": 0.0,
            "balance_flops_per_byte": 0.0})
        phases = {k: dict(v) for k, v in _REPORTS.items()
                  if k != "_machine"}
    return {"machine": machine, "phases": phases,
            "tick_anatomy": serving_tick_anatomy()}


def reset_serving_roofline():
    """Drop every stashed phase report (test hygiene)."""
    with _REPORTS_LOCK:
        _REPORTS.clear()
