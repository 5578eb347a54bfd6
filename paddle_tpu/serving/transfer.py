"""KV handoff between engine replicas (disaggregated prefill/decode).

DistServe/Splitwise-style split: a prefill-role replica runs admission +
chunked prefill, then its finished sequences move to a decode-role
replica. The unit of transfer is a :class:`KVPayload` — the sequence's
KV blocks gathered out of the source pool into a dense ``[L, max_blocks,
block_size, H_kv, D]`` tensor pair plus the host bookkeeping needed to
resume decoding bit-exactly (cur/gen/last_tok).

:class:`KVTransfer` is the seam a real multi-host wire plugs into
(ProcessGroupNCCL send/recv in the Paddle stack, a device collective
over the mesh here). The in-process :class:`DeviceKVTransfer` is a
``jax.device_put`` onto the target pool's device — a device-to-device
copy when replicas live on different devices, a no-op view otherwise.

Both jitted programs here are fixed-shape per (engine geometry), so
repeated handoffs never recompile: gather pads the block-index vector
to ``max_blocks_per_seq`` (extra rows are gathered then ignored),
install pads with the ``num_blocks`` sentinel so the donating scatter
drops them.

The handoff is hardened against a lossy wire (ISSUE 16): the source
engine seals each payload (:meth:`KVPayload.seal`) with its expected
geometry plus per-tensor checksums, and the router runs
:func:`validate_payload` on the shipped copy before install — a
truncated or corrupted transfer raises :class:`KVTransferError` and is
retried from the pristine source payload under a
:class:`TransportPolicy` (deadline, bounded exponential backoff,
straggler hedging to another decode replica).
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu.serving.types import Request


class KVTransferError(RuntimeError):
    """A shipped payload failed geometry/checksum validation — a
    partial or corrupted transfer. The handoff is retried from the
    pristine source payload; the rejected copy is never installed."""


def _tensor_checksum(x) -> float:
    """Order-independent content checksum: the f32 sum of all elements.
    Cheap (one reduce), device-friendly, and any zeroed/truncated block
    row of real KV activations moves it far past tolerance."""
    return float(jnp.sum(jnp.asarray(x, jnp.float32)))


@dataclass
class KVPayload:
    """One prefilled sequence in flight between replicas."""
    req: Request
    cur: int                 # tokens stored in the source cache
    gen: int                 # tokens generated so far (1 after prefill)
    last_tok: int            # sampled but not yet written to cache
    n_blocks: int            # leading rows of k/v that are real
    block_size: int
    k: object                # [L, max_blocks, block_size, H_kv, D]
    v: object
    # quantized pools (ISSUE 17): int8 codes above are meaningless
    # without their per-(position, kv-head) scales — the scale rows ride
    # the same wire as [L, max_blocks, block_size, H_kv] f32 (None for
    # model-dtype pools)
    k_scale: object = None
    v_scale: object = None
    # filled by seal(): what the payload looked like when it left the
    # source pool — validate_payload checks the shipped copy against it
    expect: dict = None

    @property
    def tokens_bytes(self):
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n

    def seal(self):
        """Record the wire contract at the source: geometry + content
        checksums (scales included for quantized payloads — a corrupted
        scale row silently rescales whole positions). Called once by
        ``extract_sequence`` before the payload leaves the engine."""
        self.expect = {
            "shape": tuple(self.k.shape),
            "cur": self.cur,
            "n_blocks": self.n_blocks,
            "ksum": _tensor_checksum(self.k),
            "vsum": _tensor_checksum(self.v),
            "quant": self.k_scale is not None,
        }
        if self.k_scale is not None:
            self.expect["kssum"] = _tensor_checksum(self.k_scale)
            self.expect["vssum"] = _tensor_checksum(self.v_scale)
        return self


def validate_payload(payload: KVPayload, target_engine) -> KVPayload:
    """Reject partial/corrupt transfers before they touch the target
    pool. Geometry is checked against both the seal and the target
    engine; checksums against the seal (tolerance covers f32 summation
    order, not content). Unsealed payloads (hand-built in tests, or a
    custom transport that re-packs) get the geometry checks only."""
    k, v = payload.k, payload.v
    pool = target_engine.cache.k_pools[0]
    if tuple(k.shape) != tuple(v.shape):
        raise KVTransferError(
            f"k/v geometry diverged in flight: {tuple(k.shape)} vs "
            f"{tuple(v.shape)}")
    if k.shape[0] != len(target_engine.cache.k_pools) \
            or tuple(k.shape[2:]) != tuple(pool.shape[1:]):
        raise KVTransferError(
            f"payload geometry {tuple(k.shape)} does not match the "
            f"target pool [{len(target_engine.cache.k_pools)}, *, "
            f"{tuple(pool.shape[1:])}]")
    if payload.n_blocks * payload.block_size < payload.cur:
        raise KVTransferError(
            f"payload truncated: {payload.n_blocks} blocks × "
            f"{payload.block_size} cannot cover cur={payload.cur}")
    # quantized-pool compatibility: int8 codes must land in an int8
    # pool WITH their scales; a bf16 payload must not target one
    quant_target = bool(getattr(target_engine.cache, "k_scales", ()))
    quant_payload = payload.k_scale is not None
    if quant_target != quant_payload:
        raise KVTransferError(
            f"KV dtype mismatch: payload is "
            f"{'int8+scales' if quant_payload else 'model-dtype'} but the "
            f"target pool is "
            f"{'int8+scales' if quant_target else 'model-dtype'} — "
            "replicas in one handoff group must share kv_dtype")
    if jnp.asarray(k).dtype != pool.dtype:
        raise KVTransferError(
            f"payload element dtype {jnp.asarray(k).dtype} != target "
            f"pool dtype {pool.dtype}")
    if quant_payload and (tuple(payload.k_scale.shape) != tuple(k.shape[:4])
                          or tuple(payload.v_scale.shape)
                          != tuple(v.shape[:4])):
        raise KVTransferError(
            f"scale geometry {tuple(payload.k_scale.shape)} does not "
            f"match the code blocks {tuple(k.shape[:4])}")
    exp = payload.expect
    if exp is not None:
        if (tuple(k.shape) != exp["shape"] or payload.cur != exp["cur"]
                or payload.n_blocks != exp["n_blocks"]):
            raise KVTransferError(
                f"payload drifted from its seal: shape={tuple(k.shape)} "
                f"cur={payload.cur} n_blocks={payload.n_blocks}, sealed "
                f"{exp['shape']}/{exp['cur']}/{exp['n_blocks']}")
        if exp.get("quant", False) != quant_payload:
            raise KVTransferError(
                "payload quantization drifted from its seal (scales "
                "added or dropped in flight)")
        checks = [(k, exp["ksum"], "k"), (v, exp["vsum"], "v")]
        if quant_payload:
            checks += [(payload.k_scale, exp["kssum"], "k-scale"),
                       (payload.v_scale, exp["vssum"], "v-scale")]
        for x, want, name in checks:
            got = _tensor_checksum(x)
            if abs(got - want) > 1e-3 * max(1.0, abs(want)):
                raise KVTransferError(
                    f"{name}-checksum mismatch (partial/corrupt "
                    f"transfer): got {got!r}, sealed {want!r}")
    return payload


class TransportPolicy:
    """Retry/deadline/hedging policy for one handoff delivery.

    ``deadline_s=None`` derives the straggler deadline from live data:
    ``deadline_margin ×`` the p95 of ``router_kv_transfer_seconds``
    (floored at ``min_deadline_s``), once at least ``min_samples``
    deliveries have been observed — before that there is no deadline
    and no hedging, so cold starts never hedge on noise. Retries use
    bounded exponential backoff through the injectable ``sleep``."""

    def __init__(self, *, deadline_s: float = None,
                 deadline_margin: float = 3.0,
                 min_deadline_s: float = 0.05, min_samples: int = 8,
                 max_attempts: int = 3, backoff_base_s: float = 0.005,
                 backoff_max_s: float = 0.1, hedge: bool = True,
                 sleep=time.sleep):
        if max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
        self.deadline_s = deadline_s
        self.deadline_margin = deadline_margin
        self.min_deadline_s = min_deadline_s
        self.min_samples = min_samples
        self.max_attempts = max_attempts
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.hedge = hedge
        self.sleep = sleep

    def backoff_s(self, attempt: int) -> float:
        """Backoff before retry ``attempt+1`` (attempt is 0-based)."""
        return min(self.backoff_base_s * (2 ** attempt), self.backoff_max_s)

    def deadline(self, hist) -> float:
        """The straggler deadline, or None while underinformed."""
        if self.deadline_s is not None:
            return self.deadline_s
        count = sum(s.count for s in hist._series.values())
        if count < self.min_samples:
            return None
        p95 = hist.quantile(0.95)
        if p95 != p95:                       # NaN: no data
            return None
        return max(self.min_deadline_s, self.deadline_margin * p95)


def _gather_blocks(k_pools, v_pools, idx):
    # also reused over the SCALE pools of a quantized cache — the
    # trailing dims differ, so each use compiles its own entry
    k = jnp.stack([p[idx] for p in k_pools])
    v = jnp.stack([p[idx] for p in v_pools])
    return k, v


_GATHER_BLOCKS_JIT = jax.jit(_gather_blocks)


def _install_blocks(cache, idx, k, v, ks, vs, slot, row, cur):
    """``ks``/``vs`` are the per-(position, kv-head) scale blocks of a
    quantized payload, or None — the None arms are distinct pytree
    structures, so one jit serves both pool flavours."""
    k_pools = [p.at[idx].set(k[li], mode="drop")
               for li, p in enumerate(cache.k_pools)]
    v_pools = [p.at[idx].set(v[li], mode="drop")
               for li, p in enumerate(cache.v_pools)]
    k_scales, v_scales = cache.k_scales, cache.v_scales
    if ks is not None:
        k_scales = tuple(p.at[idx].set(ks[li], mode="drop")
                         for li, p in enumerate(cache.k_scales))
        v_scales = tuple(p.at[idx].set(vs[li], mode="drop")
                         for li, p in enumerate(cache.v_scales))
    tables = cache.block_tables.at[slot].set(row)
    lens = cache.lens.at[slot].set(cur)
    return type(cache)(k_pools, v_pools, tables, lens, k_scales, v_scales,
                       cache.passes)


_INSTALL_BLOCKS_JIT = jax.jit(_install_blocks, donate_argnums=(0,))

# these jits trace over the cache pytree: clear_jit_caches() must reach
# them too
from paddle_tpu.models.paged import _EXTRA_CLEAR as _PAGED_EXTRA_CLEAR  # noqa: E402

_PAGED_EXTRA_CLEAR.extend([_GATHER_BLOCKS_JIT, _INSTALL_BLOCKS_JIT])


class KVTransfer:
    """Moves a payload's tensors onto the target replica's device. The
    base class is the identity wire (same process, same device) — a
    multi-host deployment subclasses ``ship`` with its RDMA/collective
    transport; everything above this seam is transport-agnostic."""

    def ship(self, payload: KVPayload, target_engine) -> KVPayload:
        return payload


class DeviceKVTransfer(KVTransfer):
    """In-process device-to-device copy: place the gathered blocks on
    whatever device holds the target engine's pool (jax makes this a
    direct D2D copy when source and target differ, a no-op view when
    they share a device — the single-host test/bench case)."""

    def ship(self, payload: KVPayload, target_engine) -> KVPayload:
        pool = target_engine.cache.k_pools[0]
        devs = getattr(pool, "devices", None)
        dev = next(iter(devs())) if callable(devs) else None
        if dev is not None:
            payload.k = jax.device_put(payload.k, dev)
            payload.v = jax.device_put(payload.v, dev)
            if payload.k_scale is not None:
                payload.k_scale = jax.device_put(payload.k_scale, dev)
                payload.v_scale = jax.device_put(payload.v_scale, dev)
        return payload
