"""Continuous-batching LLM serving engine.

Ref capability: PaddleNLP ``llm/predict/predictor.py`` block-attention
serving (request queue + block KV cache + ``fused_multi_transformer``'s
block cache ops). TPU-native split:

  * DEVICE — :class:`~paddle_tpu.serving.executor.ModelExecutor`: the
    fixed-shape jitted programs from ``models/paged.py`` (slot-aware
    prefill, chunked prefill/verify, the fused decode tick). Shapes
    never change across ticks, so nothing recompiles.
  * HOST — :class:`~paddle_tpu.serving.scheduler.Scheduler` (FCFS
    queue, deadlines, preemption policy, backpressure) and
    :class:`~paddle_tpu.serving.kv.KVManager` (block tables, prefix
    cache, the reservation ledger). All per-tick bookkeeping is
    vectorised numpy; the only per-tick device→host traffic is the
    [num_slots] sampled-token fetch.

``LLMEngine`` orchestrates the three: slot state lives here, policy in
the scheduler, block accounting in the KV manager, device state in the
executor. The pre-split attribute surface (``engine.mgr``,
``engine.queue``, ``engine._reserved``, ...) is preserved as
delegating properties — external callers and tests see the same API
the monolithic ``serving.py`` exposed.

Capacity discipline: a request is admitted only when the pool can cover
its WHOLE worst case (prompt + max_new_tokens) net of other in-flight
reservations — blocks are still allocated lazily (pool usage ≈ Σ live
lengths), but an admitted request can never hit an out-of-blocks
condition mid-decode (there is no preemption to recover with).

Multi-replica serving (ISSUE 7): ``prefill_only=True`` stops the tick
after chunked prefill — the replica admits and prefills but never
decodes; a :class:`~paddle_tpu.serving.router.Router` extracts each
finished sequence (``extract_sequence``) and installs it into a
decode-role replica (``install_sequence``) via the KV-transfer seam.
"""
from __future__ import annotations

import os
import time
from contextlib import contextmanager
from dataclasses import replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.paged import (LATENT_LAYER, LINEAR_LAYER,
                                     _beam_finalize, _BEAM_SELECT_JIT,
                                     cache_passes,
                                     greedy_accept_length, is_moe_model,
                                     kv_windows, layer_kinds, state_bytes,
                                     stochastic_accept_row,
                                     window_space_layers)
from paddle_tpu.observability import span as _span
from paddle_tpu.observability.flight import FLIGHT
from paddle_tpu.observability.goodput import GOODPUT
from paddle_tpu.observability.requests import REQUESTS
from paddle_tpu.observability.roofline import (ModelGeometry,
                                               record_serving_throughput,
                                               resolve_serving_peaks)
from paddle_tpu.serving.executor import ModelExecutor, _SAMPLE_ROWS_JIT  # noqa: F401  (re-exported)
from paddle_tpu.serving.executor import LATENT_MODEL as _LATENT
from paddle_tpu.serving.executor import MIXED_MODEL as _MIXED
from paddle_tpu.serving.executor import STATEFUL_MODEL as _STATEFUL
from paddle_tpu.serving.kv import KVManager, cache_block_bytes
from paddle_tpu.serving.scheduler import Scheduler
from paddle_tpu.serving.cp import (_CP_AXIS, _CP_GATHER_S,
                                   _CP_SHARD_BLOCKS, shard_occupancy)
from paddle_tpu.serving.degrade import SessionSnapshot
from paddle_tpu.serving.telemetry import (_ACTIVE_SLOTS, _ASYNC_DEPTH,
                                          _ASYNC_DRAINS, _CANCELLED,
                                          _DRAIN, _FINISHED,
                                          _GRAMMAR_SPEC_REJECTS,
                                          _GRAMMAR_TOKENS, _KV_IN_USE,
                                          _KV_UTIL, _QUEUE_DEPTH,
                                          _REJECTED, _SAMPLER_CALLS,
                                          _SNAPSHOTS,
                                          _SPEC_ACCEPTED,
                                          _SPEC_DRAFT_REUSE,
                                          _SPEC_FALLBACKS,
                                          _SPEC_PROPOSED, _SPEC_RATE,
                                          _SPEC_TOKENS, _STATE_BYTES,
                                          _TENANT_FINISHED,
                                          _TENANT_REJECTED, _TENANT_TOK_LAT,
                                          _TENANT_TOKENS, _TENANT_TTFT,
                                          _TICK, _TICK_BREAKDOWN,
                                          _TICK_HIDDEN, _TIMEOUTS,
                                          _TOK_LAT, _TOKENS,
                                          _TTFT, _WINDOW_KV_IN_USE,
                                          _WINDOW_RECYCLED, tenant_label)
from paddle_tpu.serving.transfer import (KVPayload, _GATHER_BLOCKS_JIT,
                                         _INSTALL_BLOCKS_JIT)
from paddle_tpu.serving.types import (EngineDrainingError, OverloadError,
                                      QueueFullError, Request, _BeamGroup)
from paddle_tpu.utils.faults import fault_point
from paddle_tpu.utils.profiler import device_memory_stats


# token-rows of a matmul that fill one pass over bf16 weights on the chips
# this serves from (v5e: 197 TFLOP/s over 819 GB/s is 240 FLOP a byte, a
# token-row of a bf16 matmul 1 FLOP a weight byte), to the power of two above
_RIDGE_TOKENS = 256

_HYBRID_HANDOFF = ("the KV handoff (extract_sequence / install_sequence): "
                   "serving/transfer.py ships K/V blocks, not the recurrent "
                   "state that goes with them")

_LATENT_HANDOFF = ("the KV handoff (extract_sequence / install_sequence): "
                   "serving/transfer.py ships a K block and a V block a "
                   "layer, a latent pool holds one array")

_MIXED_HANDOFF = ("the KV handoff (extract_sequence / install_sequence): "
                  "serving/transfer.py ships the blocks of one table, a "
                  "sequence here has one in each of two block spaces")

_LOOPED_HANDOFF = ("the KV handoff (extract_sequence / install_sequence): "
                   "its payload holds one row a block and layer, a looped "
                   "pool one a pass as well")


class LLMEngine:
    """Continuous-batching engine over a shared paged KV pool.

    ``num_slots`` concurrent sequences; queued requests are admitted
    MID-FLIGHT into slots freed by finished ones (prefill interleaves with
    decode ticks). ``step()`` is one engine tick; ``run()`` drains
    everything and returns {req_id: full token list}.
    """

    def __init__(self, model, *, num_slots=8, block_size=16,
                 max_prompt_len=128, max_seq_len=None, num_blocks=None,
                 eos_token_id=None, temperature=0.0, top_k=None, top_p=None,
                 seed=0, prefix_caching=True, preemption=False,
                 max_queue_len=None, clock=None, draft_model=None,
                 spec_k=4, spec_adaptive=True, prefill_only=False,
                 adapter_store=None, degrade=None, slo=None, kv_dtype=None,
                 cp=1, async_depth=0, num_state_snapshots=0,
                 num_window_blocks=None):
        # the model itself goes to the executor, which flattens it once:
        # the engine serves the weights it was built with
        cfg = self.cfg = model.cfg
        # quantized KV cache (ISSUE 17): kv_dtype="int8" stores the block
        # pools as int8 with per-(position, kv-head) f32 scale pools.
        self.kv_dtype = kv_dtype
        # context-parallel serving (ISSUE 18): cp>1 shards the paged KV
        # pool's physical blocks over a cp-wide mesh; prefill partials
        # merge via ring/Ulysses and decode merges via psum. cp=1 is the
        # single-device path.
        cp = int(cp)
        if cp < 1:
            raise ValueError(f"cp must be >= 1, got {cp}")
        self.cp = cp
        # async pipelined decode (ISSUE 20): async_depth=K keeps up to K
        # decode ticks dispatched-but-unfetched; the tick's output token
        # array stays ON DEVICE feeding the next tick's last_tok while
        # the previous tick's tokens are fetched/emitted on the host,
        # hidden under the in-flight dispatch (PR 3's deferred-sync
        # contract, serving-side). async_depth=0 traces the synchronous
        # programs alone.
        async_depth = int(async_depth)
        if async_depth < 0:
            raise ValueError(
                f"async_depth must be >= 0, got {async_depth}")
        self.async_depth = async_depth
        self.num_slots = num_slots
        self.block_size = block_size
        # the window of the model's window layers (None: it has none; a
        # layer's window is its kind's, ``models.paged.kv_windows``): the
        # paged kernels read a row's last ``window`` positions there, so
        # the blocks below them are recycled, and a row holds O(window)
        # blocks in those layers. ``mixed``: window layers beside full
        # ones, whose blocks live in a space of their own (a second table
        # a row, a second manager: ``kv.window``) of ``num_window_blocks``
        # blocks; the full space is ``num_blocks`` and everything that was
        # one space's stays its. A model whose every layer is windowed
        # (Mistral v0.1's shape) has one space, recycled as it always was.
        self.window = next((w for w in kv_windows(cfg) if w is not None),
                           None)
        self.mixed = bool(window_space_layers(cfg))
        if self.mixed:
            # what is not built over two spaces, each by what it would take
            self._refuse(
                _MIXED,
                prefix_caching and "prefix caching (pass prefix_caching="
                "False): a hit needs the window layers' rows of the last "
                f"{self.window} positions kept under the trie's node, and "
                "recycling frees them",
                draft_model is not None and "a draft model: the verify "
                "program is handed one space's tables, and a rejected "
                "proposal may lie in a block the window space has recycled",
                cp > 1 and "context parallelism (cp > 1): the window space's "
                "blocks are not laid out shard by shard",
                adapter_store is not None and "multi-LoRA (adapter_store): "
                "its adapters are written for the full layers' attention "
                "alone",
                async_depth > 0 and "async_depth > 0: the pipelined tick "
                "grows no tables, and the window space's recycle every tick",
                kv_dtype is not None and "a quantized K/V pool (kv_dtype): "
                "no scale pools are built for the window space",
                preemption and "preemption=True: the replay of a preempted "
                "row over two block spaces is not tested")
        # graceful degradation (ISSUE 16): an optional shared
        # DegradationController — consulted by the spec gate, the
        # chunked-prefill budget, admission shedding, and the session
        # gate. None (the default) means full service, always.
        self.degrade = degrade
        # per-tenant SLO tracking + usage metering (ISSUE 19): an
        # optional shared SLOTracker — charged per tick from step(),
        # polled from the gauge sweep. None means no tracking, ever.
        self.slo = slo
        self.max_prompt_len = max_prompt_len
        self.max_seq_len = max_seq_len or (max_prompt_len + 256)
        self.max_blocks_per_seq = -(-self.max_seq_len // block_size)
        if num_blocks is None:
            num_blocks = num_slots * self.max_blocks_per_seq
        # MoE models route tokens through expert all_to_alls inside the
        # tick — give chaos a hook at that boundary (dead expert shard)
        self._is_moe = is_moe_model(model)
        if self.cp > 1:
            if self._is_moe:
                raise NotImplementedError(
                    "context-parallel serving (cp>1) does not compose with "
                    "MoE models yet — the expert all_to_all would need its "
                    "own mesh axis")
            if adapter_store is not None:
                raise NotImplementedError(
                    "context-parallel serving (cp>1) does not compose with "
                    "multi-LoRA (adapter_store) yet — per-slot adapter "
                    "gathers are not sharded over cp")
            # each shard owns num_blocks/cp physical blocks — round the
            # pool up so the contiguous split is exact
            num_blocks = -(-num_blocks // self.cp) * self.cp
        self.eos_token_id = eos_token_id
        # engine defaults; each request may override temperature/top_p
        # (top_k stays engine-global — it is a static compile parameter)
        self.default_temp = float(temperature)
        self.default_top_p = 1.0 if top_p is None else float(top_p)
        self.top_k = top_k
        self.temps = np.zeros(num_slots, np.float32)
        self.top_ps = np.ones(num_slots, np.float32)
        self._dyn_rope = (getattr(cfg, "rope_scaling", None)
                          or {}).get("type") == "dynamic"
        # prefix caching is sound only when a block's KV is a function of
        # its token prefix alone: windowed recycling punches holes in the
        # table, and dynamic-NTK makes KV depend on the FULL prompt length
        self.prefix_caching = bool(prefix_caching) and self.window is None \
            and not self._dyn_rope
        # preemption: admit optimistically (no worst-case reservation for
        # greedy requests; beams keep theirs) and, on out-of-blocks,
        # preempt the youngest greedy slot — it re-queues with
        # resume-prompt = prompt + generated-so-far and recomputes
        self.preemption = bool(preemption)
        # prefill-role replica (disaggregated serving): the tick stops
        # after chunked prefill — slots activate with their first token
        # but NEVER decode here; the router extracts and ships them
        self.prefill_only = bool(prefill_only)
        # replica name for request-tracker events; the Router stamps the
        # replica name here so cross-replica timelines stitch (ISSUE 9)
        self.trace_name = None

        # ---- speculative decoding (ISSUE 5): draft-and-verify tick ----
        # ``draft_model`` enables it; each eligible slot drafts up to
        # spec_k tokens through a per-slot dense draft cache, then ONE
        # batched target chunk forward verifies them through the paged
        # pool. Beam slots always take the one-token path.
        self.draft_model = draft_model
        self.spec_k = int(spec_k)
        self.spec_adaptive = bool(spec_adaptive)
        if draft_model is not None:
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if (self.window is not None and not self.mixed) or \
                    getattr(draft_model.cfg, "sliding_window", None):
                raise NotImplementedError(
                    "speculative decoding needs full (un-windowed) caches "
                    "on both models — rewind relies on masked stale KV")
            if self._dyn_rope:
                raise NotImplementedError(
                    "speculative decoding with dynamic-NTK rope is not "
                    "supported (the verify chunk shares the chunked-"
                    "prefill forward, which refuses per-chunk bases)")
            if draft_model.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab_size} != target "
                    f"vocab {cfg.vocab_size}")
            # host RNG for draft sampling + accept/reject (temperature>0):
            # the accept rule preserves the target distribution for any
            # uniform source, so this stream need not match the engine key
            self._spec_rs = np.random.RandomState((seed ^ 0x5eed) & 0x7fffffff)

        # a looped model (its stack run ``total_ut_steps`` times a token,
        # K/V of every pass kept): what does not compose with it yet
        self.ut_steps = cache_passes(cfg)
        self._looped = (f"a looped model ({self.ut_steps} passes over "
                        f"{cfg.num_hidden_layers} layers)")
        if self.ut_steps > 1:
            self._refuse(
                self._looped,
                self.cp > 1 and "context parallelism (cp > 1): the pools' "
                "rows are laid out pass by pass, not shard by shard",
                adapter_store is not None and "multi-LoRA (adapter_store): "
                "its stacked adapter tensors are indexed by layer, not by "
                "(pass, layer)",
                draft_model is not None and "a draft model: a rejected "
                "proposal would have to rewind every pass",
                getattr(cfg, "early_exit_threshold", 1.0) < 1 and
                "early_exit_threshold < 1: a token's passes would vary, "
                "and the scheduler counts one fixed cost a slot and tick")

        # a model with recurrent layers (``layer_types`` names them): each
        # slot owns a state beside its K/V (zeroed by the program that
        # starts its prompt, rebuilt by the replay after a preemption), and
        # a prefix hit is worth only as far as a snapshot of that state
        # exists; ``num_state_snapshots`` is the capacity of their pool,
        # one constructor size as ``num_blocks`` is one
        kinds = layer_kinds(cfg) or ()
        self.stateful = LINEAR_LAYER in kinds
        self.num_state_snapshots = (int(num_state_snapshots)
                                    if self.stateful else 0)
        if self.stateful:
            self._refuse(
                _STATEFUL,
                draft_model is not None and "a draft model: a rejected "
                "token's write to the recurrent state cannot be rolled back",
                self.cp > 1 and "context parallelism (cp > 1): the state "
                "is a slot's, not a shard's",
                adapter_store is not None and "multi-LoRA (adapter_store): "
                "its adapters are written for attention's projections",
                self.async_depth > 0 and "async_depth > 0: the pipelined "
                "tick has not been run over a recurrent state",
                kv_dtype is not None and "a quantized K/V pool (kv_dtype): "
                "not calibrated beside a float32 state")

        # a model whose layers keep latent rows (multi-head latent
        # attention): one pool a layer, read by all heads; block ids, the
        # trie, copy-on-write and the scheduler are per block as for K/V
        self.latent = LATENT_LAYER in kinds
        if self.latent:
            self._refuse(
                _LATENT,
                self.cp > 1 and "context parallelism (cp > 1): the latent "
                "kernels emit no partials to merge across shards",
                kv_dtype is not None and "a quantized K/V pool (kv_dtype): "
                "no scales are kept for a latent row",
                draft_model is not None and "a draft model: no test has "
                "rewound a latent pool past a rejected proposal",
                adapter_store is not None and "multi-LoRA (adapter_store): "
                "its adapters are written for a fused qkv projection",
                self.async_depth > 0 and "async_depth > 0: the pipelined "
                "tick has not been run over a latent pool")

        # ---- the three extracted layers ----
        # two spaces: the window space holds, for every slot at once, what
        # a window layer reads plus one chunk (a row's most: a chunk at
        # offset o keeps the rows from o - window on until it has run)
        self._window_row_blocks = ((self.window + max_prompt_len)
                                   // block_size + 2) if self.mixed else 0
        if self.mixed and num_window_blocks is None:
            num_window_blocks = num_slots * self._window_row_blocks
        self.kv = KVManager(num_blocks, block_size,
                            int(num_window_blocks) if self.mixed else 0)
        # the space whose blocks below the window are recycled
        self._wspace = self.kv.window if self.mixed else self.kv.mgr
        if self.stateful:
            self.kv.keep_state(self.num_state_snapshots)
        self._block_bytes = None     # per-block HBM bytes, lazily computed
        self._dev_mem_t = None       # last device_memory_stats refresh
        self.sched = Scheduler(max_queue_len=max_queue_len, clock=clock)
        self.exe = ModelExecutor(
            model, num_slots=num_slots, num_blocks=num_blocks,
            block_size=block_size, max_blocks_per_seq=self.max_blocks_per_seq,
            top_k=top_k, seed=seed, draft_model=draft_model,
            spec_k=self.spec_k, max_seq_len=self.max_seq_len,
            kv_dtype=kv_dtype, cp=self.cp,
            num_state_snapshots=self.num_state_snapshots,
            window_blocks=num_window_blocks if self.mixed else None)

        # host mirrors (vectorised bookkeeping — no per-token python loops)
        self.slot_req = np.full(num_slots, -1, np.int64)   # req_id or -1
        self.active = np.zeros(num_slots, bool)
        self.cur = np.zeros(num_slots, np.int64)     # tokens stored in cache
        self.gen = np.zeros(num_slots, np.int64)     # tokens generated
        self.max_gen = np.zeros(num_slots, np.int64)
        self.table_len = np.zeros(num_slots, np.int64)
        self.last_tok = np.zeros(num_slots, np.int32)

        # ---- multi-tenant serving (ISSUE 14) ----
        # ``adapter_store``: a shared AdapterStore; a request carrying an
        # adapter_id is admitted only once its adapter is device-resident
        # AND pinned (the scheduler acquires it), and every per-slot
        # forward adds the grouped rank-r correction for that slot's
        # cache index. With no store, or no adapter-carrying rows, the
        # forwards are handed lora=None and trace EXACTLY the base programs.
        self.adapter_store = adapter_store
        self.slot_aidx = np.full(num_slots, -1, np.int64)  # cache idx / -1
        self._adapter_pins: dict[int, object] = {}   # rid -> adapter_id
        # grammar-constrained decoding: slot -> [automaton, state]. The
        # state advances in ``_emit`` as tokens commit, so it is always
        # the state AFTER everything in req.tokens — a pure function of
        # the emitted stream (resume/install replays it).
        self._grammar: dict[int, list] = {}

        # spec-decode per-slot state (allocated tiny even when spec is
        # off, so reset sites need no guards). ``draft_cur``: committed-
        # sequence positions 0..draft_cur-1 are in the draft cache — 0
        # means empty, which is how eviction "frees" a draft cache and
        # replay rebuilds it (the re-admitted slot re-feeds from scratch).
        self.draft_cur = np.zeros(num_slots, np.int64)
        self.slot_k = np.full(num_slots, self.spec_k, np.int64)
        self._acc_ema = np.ones(num_slots, np.float64)
        # draft-cache reuse across sessions of a slot (ISSUE 11): the
        # token ids whose K/V currently sit in the draft cache rows
        # 0..draft_cur-1, snapshotted host-side at each commit. A new
        # request whose radix-adopted prefix matches the resident ids
        # seeds draft_cur past the match instead of re-feeding from 0.
        self._draft_resident: dict[int, np.ndarray] = {}
        # per-slot adopted span of the CURRENT request: the draft
        # catch-up feed bills only re-embeds inside this span as
        # replay_prefill waste (first-time prompt embedding is not waste)
        self._adopted_span = np.zeros(num_slots, np.int64)

        self.is_beam = np.zeros(num_slots, bool)
        self.groups: dict[int, _BeamGroup] = {}
        self._sid_counter = 0        # unique fork keys: (req_id, counter)
        # chunked prefill (prompts > max_prompt_len): rid -> (slot,
        # tokens consumed); slots stay inactive until the last chunk
        self.prefilling: dict[int, tuple] = {}

        self._staged_admits = frozenset()   # this tick's pre-scatter rows
        # rows a call of the two prefill programs: the fewest that fill one
        # pass over the weights (about _RIDGE_TOKENS token-rows), so a tick
        # that admits one prompt does not compute a row for every slot; a
        # tick with more live rows sends ceil(live / prefill_rows) calls
        self.prefill_rows = min(num_slots,
                                max(1, _RIDGE_TOKENS // max_prompt_len))
        self._prefill_sent = [0, 0]    # this tick's [live rows, calls]
        # and, for each of its calls that chose a first token: was it greedy
        self._prefill_greedy = []
        # host-vs-device split of decode ticks (admission ticks excluded):
        # stats["host_s"] is scheduling/bookkeeping, stats["device_s"] the
        # jitted tick incl. the [num_slots] token fetch
        self.stats = {"host_s": 0.0, "device_s": 0.0, "ticks": 0,
                      "preemptions": 0, "timeouts": 0, "cancelled": 0,
                      "rejected": 0, "spec_ticks": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_fallbacks": 0,
                      # not a counter: HBM bytes one token holds in the
                      # pool, over every cache layer (a (pass, layer) pair
                      # of a looped model), scale pools included
                      "cache_bytes_per_token":
                          cache_block_bytes(self.cache) // block_size,
                      # likewise: bytes of recurrent state one slot holds
                      # over every linear layer (0: every layer keeps K/V)
                      "state_bytes_per_slot": state_bytes(self.cache.states)}
        self._adm_counter = 0                # admission recency, per slot
        self.adm_order = np.zeros(num_slots, np.int64)

        # ---- roofline ledger (ISSUE 12): cumulative per-phase
        # [seconds, tokens, weight passes, KV-read positions], folded
        # into serving_mfu/mbu/arith_intensity at each gauge sweep.
        # Peaks resolve once from device 0 (0.0 off-TPU → gauges read
        # 0.0 = undefined; PT_ROOFLINE_KIND overrides for what-if).
        # _tick_phase holds the CURRENT tick's wall-time split; step()
        # folds it into the breakdown histogram and these accumulators.
        def _geom(m, cache=None):
            try:
                g = ModelGeometry.from_config(
                    m.cfg, dtype_bytes=jnp.dtype(m.cfg.dtype).itemsize)
            except Exception:
                return None      # adapter without a full config: no ledger
            # quantized serving (ISSUE 17): bill the ACTUAL storage
            # dtypes — int8 pools carry 1-byte codes + a 4-byte
            # per-(position, kv-head) scale, weight-only models stream
            # bits/8 bytes per param — or MBU would be overstated 2x
            kw = {}
            if cache is not None and getattr(cache, "k_scales", ()):
                kw.update(kv_dtype_bytes=cache.k_pools[0].dtype.itemsize,
                          kv_scale_bytes=4)
            bits = getattr(m, "_wo_bits", None)
            if bits:
                kw["weight_dtype_bytes"] = bits / 8.0
            # context parallelism (ISSUE 18): bill the per-token
            # cross-shard merge traffic in the decode bytes model
            if self.cp > 1 and cache is not None:
                kw["cp"] = self.cp
            return _dc_replace(g, **kw) if kw else g
        self._geom = _geom(model, self.exe.cache)
        self._draft_geom = _geom(draft_model) if draft_model is not None \
            else None
        self._peak_flops, self._peak_hbm = resolve_serving_peaks(
            jax.devices()[0])
        self._phase_acc = {p: [0.0, 0, 0, 0] for p in
                           ("prefill", "decode", "spec_draft", "spec_verify")}
        # the same sums as the last whole tick left them: what a sweep in
        # the shadow of a tick pushes, that tick's seconds being unknown
        self._roofline_sums: dict[str, tuple] = {}
        self._tick_phase: dict[str, float] = {}
        self._tick_no = 0                 # the ``serving.step`` span's arg

        # ---- async pipeline window (ISSUE 20) ----
        # _async_win: oldest-first list of dispatched-but-unfetched ticks,
        # each {"nxt": device tokens, "ran": device mask, "rng_before":
        # the executor rng BEFORE that tick's split}. _async_dev holds
        # the device-resident loop state (tokens/stop/gen/max_gen/active)
        # threading tick N's outputs into tick N+1 without a host round
        # trip; None whenever the window is empty. _async_rewound guards
        # the one-shot rng rewind when draining a fully-masked tick.
        self._async_win: list[dict] = []
        self._async_dev = None
        self._async_rewound = False
        self._async_draining = False
        # gauge-sweep throttle (PT_GAUGE_EVERY_S): wall-clock of the last
        # sweep, a force flag set wherever a request leaves its place
        # (finish, cancel, expiry, preemption, an async drain, a tick
        # that raises) so that tick ends in an exact sweep, and a sweep
        # counter a test reads. _gauge_shadowed: this tick swept between
        # its decode dispatch and its fetch (_sweep_in_shadow).
        self._gauge_t = None
        self._gauge_force = False
        self._gauge_shadowed = False
        self._gauge_sweeps = 0
        # hidden host time accumulated this tick (drain work overlapped
        # with in-flight device dispatch); observed once per step().
        self._hidden_acc = 0.0
        # spec-decode D2H accounting: bytes fetched by pick_all this
        # engine lifetime (satellite: non-greedy rows gathered on device)
        self._spec_fetch_bytes = 0

    # ------------------------------------------- pre-split attribute surface
    # The monolithic serving.py exposed all of this directly on the
    # engine; tests and external callers still poke it, so every moved
    # field delegates to the layer that now owns it.
    @property
    def mgr(self):
        return self.kv.mgr

    @property
    def queue(self):
        return self.sched.queue

    @property
    def requests(self):
        return self.sched.requests

    @property
    def cache(self):
        return self.exe.cache

    @cache.setter
    def cache(self, value):
        self.exe.cache = value

    @property
    def rng(self):
        return self.exe.rng

    @rng.setter
    def rng(self, value):
        self.exe.rng = value

    @property
    def _draft_cache(self):
        return self.exe._draft_cache

    @_draft_cache.setter
    def _draft_cache(self, value):
        self.exe._draft_cache = value

    @property
    def _reserved(self):
        return self.kv.reserved

    @_reserved.setter
    def _reserved(self, value):
        self.kv.reserved = value

    @property
    def _resv(self):
        return self.kv.resv

    @property
    def _need(self):
        return self.kv.need

    @property
    def _draining(self):
        return self.sched.draining

    @_draining.setter
    def _draining(self, value):
        self.sched.draining = value

    @property
    def max_queue_len(self):
        return self.sched.max_queue_len

    @max_queue_len.setter
    def max_queue_len(self, value):
        self.sched.max_queue_len = value

    @property
    def _clock(self):
        return self.sched.clock

    @_clock.setter
    def _clock(self, value):
        self.sched.clock = value

    @property
    def _has_deadlines(self):
        return self.sched.has_deadlines

    @_has_deadlines.setter
    def _has_deadlines(self, value):
        self.sched.has_deadlines = value

    def _refuse(self, model: str, *reasons):
        """Raise for the first of ``reasons`` that is a message: the one
        place a model of a kind (``model``: ``self._looped``, ``_STATEFUL``)
        is told what it cannot be served with."""
        for why in reasons:
            if why:
                raise NotImplementedError(f"{model} is not served with {why}")

    # ------------------------------------------------------------- intake
    def add_request(self, req: Request) -> int:
        """Check and queue ``req`` -> its id. The span ``serving.submit``
        is the program's share of what a caller does between two ticks."""
        with _span("serving.submit") as sp:
            rid = self._submit(req)
            sp.set(rid=rid)
            return rid

    def _submit(self, req: Request) -> int:
        self.sched.check_backpressure(self.stats)
        # ladder L4: explicit backpressure on NEW sessions. Requests a
        # Router already accepted (_preadmitted) pass — rejecting them
        # here would double-gate dispatches and death requeues.
        if (self.degrade is not None and not req._preadmitted
                and not self.degrade.accepting_sessions()):
            self.stats["rejected"] += 1
            _REJECTED.inc(reason="degraded")
            if req.tenant_id is not None:
                _TENANT_REJECTED.inc(tenant=tenant_label(req.tenant_id))
            raise OverloadError(
                "degradation ladder at L4 — new sessions rejected, "
                "retry after the cluster recovers")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "itself produces the first token)")
        if req.num_beams < 1:
            raise ValueError("num_beams must be >= 1")
        if req.num_beams > 1:
            self._refuse(
                _STATEFUL,
                self.stateful and "beam search (num_beams > 1): a fork "
                "would have to copy a slot's recurrent state")
            self._refuse(
                _LATENT,
                self.latent and "beam search (num_beams > 1): no test has "
                "forked a latent pool's blocks")
            self._refuse(
                self._looped,
                self.ut_steps > 1 and "beam search (num_beams > 1): no "
                "test has forked a looped model's blocks")
            self._refuse(
                _MIXED,
                self.mixed and "beam search (num_beams > 1): a fork would "
                "have to share blocks in two spaces, and a recycled window "
                "block may be one a forked child still reads")
            if req.num_beams > self.num_slots:
                raise ValueError(f"num_beams {req.num_beams} exceeds "
                                 f"num_slots={self.num_slots}")
            if self.cp > 1:
                raise NotImplementedError(
                    "beam search under context parallelism (cp>1) is not "
                    "supported — the beam select needs full logprobs, "
                    "which the cp tick does not gather")
            if self.window is not None:
                raise NotImplementedError(
                    "beam search + sliding-window block recycling are not "
                    "combined (a recycled parent block may be needed by a "
                    "forked child)")
            if req.stream is not None:
                raise ValueError("streaming is not supported for beam "
                                 "requests (tokens are only known at the "
                                 "final selection)")
        if len(req.prompt) < 1:
            raise ValueError("prompt must contain at least one token "
                             "(an empty row has no logit to sample from)")
        if len(req.prompt) > self.max_prompt_len and req.num_beams > 1:
            raise ValueError(f"prompt length {len(req.prompt)} exceeds "
                             f"max_prompt_len={self.max_prompt_len} "
                             "(chunked prefill does not combine with "
                             "beam search)")
        if len(req.prompt) > self.max_prompt_len \
                and self.window is not None and not self.mixed:
            # (two block spaces chunk: a chunk reads the window layers'
            # rows of its own space, recycled after every chunk)
            raise NotImplementedError(
                "chunked prefill + sliding-window recycling not combined")
        if len(req.prompt) > self.max_prompt_len and \
                (getattr(self.cfg, "rope_scaling", None)
                 or {}).get("type") == "dynamic":
            # refuse HERE: a trace-time raise inside step() would leave
            # the slot claimed and the request wedged in self.prefilling
            raise NotImplementedError(
                "chunked prefill with dynamic-NTK rope is not supported")
        if len(req.prompt) + req.max_new_tokens > self.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        if self._worst_case_blocks(req) > self.mgr.num_blocks:
            # the request could NEVER be admitted — even a cp-scaled pool
            # (num_blocks grows ~linearly with the cp axis) cannot hold
            # its worst case. Finish it gracefully instead of raising:
            # a raise here would be fine for this caller, but the same
            # check used to wedge router/batch clients that submit
            # blindly — surface finish_reason="too_long" through the
            # normal completion path so the FCFS head never jams on it.
            rid = self.sched.enqueue(req)
            self.queue.pop()                  # never actually waits
            REQUESTS.submit(req, source="engine")
            req.done = True
            req.finish_reason = "too_long"
            self.stats["rejected"] += 1
            _REJECTED.inc(reason="too_long")
            _FINISHED.inc(reason="too_long")
            if req.tenant_id is not None:
                _TENANT_REJECTED.inc(tenant=tenant_label(req.tenant_id))
                _TENANT_FINISHED.inc(tenant=tenant_label(req.tenant_id),
                                     reason="too_long")
            FLIGHT.record("serving.reject", rid=rid, reason="too_long")
            REQUESTS.finish(req, "too_long", replica=self.trace_name)
            return rid
        if req.adapter_id is not None:
            if self.adapter_store is None:
                raise ValueError(
                    "request carries an adapter_id but the engine was "
                    "built without an adapter_store")
            if not self.adapter_store.known(req.adapter_id):
                raise ValueError(f"adapter {req.adapter_id!r} is not "
                                 "registered with the adapter store")
            if req.num_beams > 1:
                raise NotImplementedError(
                    "multi-LoRA + beam search are not combined")
        if req.grammar is not None:
            if req.num_beams > 1:
                raise NotImplementedError(
                    "grammar-constrained decoding + beam search are not "
                    "combined (beam tokens come from the select, not "
                    "the sampler)")
            if not (hasattr(req.grammar, "bias")
                    and hasattr(req.grammar, "advance")):
                raise ValueError("req.grammar must be a "
                                 "serving.grammar.TokenMaskAutomaton")
            if len(req.grammar.vocab) != self.cfg.vocab_size:
                raise ValueError(
                    f"grammar vocab {len(req.grammar.vocab)} != model "
                    f"vocab {self.cfg.vocab_size}")
        rid = self.sched.enqueue(req)
        REQUESTS.submit(req, source="engine")        # idempotent re-submit
        REQUESTS.event(req, "queued", replica=self.trace_name,
                       depth=len(self.queue))
        _QUEUE_DEPTH.set(len(self.queue))
        return rid

    def pop_finished(self) -> dict:
        """Remove and return completed requests ({req_id: Request}) — call
        periodically from a long-running serve loop so the engine does not
        retain every finished request's token list forever."""
        return self.sched.pop_finished()

    def generate(self, prompt, **kw) -> int:
        return self.add_request(Request(prompt, **kw))

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self.active.any())
                or bool(self.groups) or bool(self.prefilling)
                or bool(self._async_win))

    def outstanding(self) -> int:
        """Requests accepted but not yet finished (queued, prefilling, or
        decoding) — the router's least-outstanding-requests load signal."""
        return sum(1 for r in self.requests.values() if not r.done)

    # --------------------------------------------- cancellation/deadlines
    def _release_ledger(self, rid: int):
        self.kv.release(rid)

    def cancel(self, req_id: int, reason: str = "cancelled") -> bool:
        """Terminate a request wherever it currently lives — queued,
        chunk-prefilling, decoding, or mid-beam — freeing its blocks,
        reservation, and slot(s). Exception-atomic: every mutation below
        is a host dict/array op ordered so a failure cannot strand
        half-released state. Safe between ``step()`` calls (and from
        stream callbacks: an emptied slot is skipped by ``_emit``).
        Returns False for unknown/finished requests."""
        req = self.requests.get(req_id)
        if req is None or req.done:
            return False
        # in-flight async ticks may already hold this request's next
        # tokens: drain so the emitted stream (and the ledger) is exact
        # before its slot state is torn down. The drain can finish the
        # request (EOS/length in the window) — re-check afterwards.
        self._drain_async("cancel")
        if req.done:
            return False
        g = self.groups.get(req_id)
        sids = list(g.sid.values()) if g is not None else None
        if not self._detach(req_id):
            return False                            # mid-transition: punt
        self._release_ledger(req_id)
        self._gauge_force = True     # cancel or expiry: exact sweep
        # peak attribution survives the free above (the ledger keeps a
        # request's lifetime max past its table drop)
        peak = (sum(self.kv.take_peak(s) for s in sids) if sids
                else self.kv.take_peak(req_id))
        REQUESTS.event(req, "kv_peak", replica=self.trace_name, blocks=peak)
        req.done = True
        req.finish_reason = reason
        self.stats["timeouts" if reason == "timeout" else "cancelled"] += 1
        (_TIMEOUTS if reason == "timeout" else _CANCELLED).inc()
        _FINISHED.inc(reason=reason)
        if req.tenant_id is not None:
            _TENANT_FINISHED.inc(tenant=tenant_label(req.tenant_id),
                                 reason=reason)
        FLIGHT.record("serving.timeout" if reason == "timeout"
                      else "serving.cancel", rid=req_id)
        REQUESTS.finish(req, reason, replica=self.trace_name)
        return True

    def _detach(self, req_id: int) -> bool:
        """Free a live request's slot(s)/blocks wherever it currently is
        (queue, chunk prefill, beam group, active slot) WITHOUT touching
        the ledger or finishing it. Shared by cancel and the router's
        pull-back path. Returns False when the request holds nothing
        (unknown, or mid-transition)."""
        for i, q in enumerate(self.queue):          # still waiting
            if q.req_id == req_id:
                del self.queue[i]
                return True
        if req_id in self.prefilling:
            slot, _ = self.prefilling.pop(req_id)
            self._drop_snapshot_plan(self.requests[req_id])
            self.mgr.free(req_id)
            self.slot_req[slot] = -1
            self._release_adapter(req_id)
            return True
        if req_id in self.groups:
            g = self.groups.pop(req_id)
            for sid in g.sid.values():
                self.mgr.free(sid)
            for slot in g.slots:
                self.active[slot] = False
                self.is_beam[slot] = False
                self.slot_req[slot] = -1
            return True
        slots = np.nonzero(self.slot_req == req_id)[0]
        if not len(slots):
            return False
        slot = int(slots[0])
        self.mgr.free(req_id)
        self.active[slot] = False
        self.slot_req[slot] = -1
        self.draft_cur[slot] = 0
        self.slot_aidx[slot] = -1
        self._grammar.pop(slot, None)
        self._release_adapter(req_id)
        return True

    def release_request(self, rid: int):
        """Pull a live request OUT of the engine (router rebalancing /
        replica death): free its slot(s), blocks, and reservation, and
        forget it — WITHOUT marking it done. Returns the Request (with
        whatever tokens it generated) so the caller can re-dispatch it,
        or None for unknown/finished/mid-transition requests."""
        req = self.requests.get(rid)
        if req is None or req.done:
            return None
        self._drain_async("boundary")
        if req.done:
            return None
        g = self.groups.get(rid)
        sids = list(g.sid.values()) if g is not None else None
        if not self._detach(rid):
            return None
        self._release_ledger(rid)
        # the request leaves this engine: stamp its peak here (the next
        # replica's incarnation stamps its own; the summary takes the max)
        peak = (sum(self.kv.take_peak(s) for s in sids) if sids
                else self.kv.take_peak(rid))
        REQUESTS.event(req, "kv_peak", replica=self.trace_name, blocks=peak)
        return self.sched.release(rid)

    def _expire(self):
        self.sched.expire(self.cancel)

    def drain(self, cancel_queued: bool = False) -> dict:
        """Graceful shutdown: stop admitting (``add_request`` raises
        EngineDrainingError) but finish everything in flight; returns
        {req_id: tokens} like ``run``. ``cancel_queued=True`` also
        cancels requests still waiting for admission instead of running
        them to completion."""
        t0 = time.monotonic()
        with _span("serving.drain", cancel_queued=cancel_queued):
            self._draining = True
            if cancel_queued:
                for r in list(self.queue):
                    self.cancel(r.req_id)
            while self.has_work():
                self.step()
            self._refresh_gauges(force=True)
        _DRAIN.observe(time.monotonic() - t0)
        return {rid: r.tokens for rid, r in self.requests.items()}

    def assert_quiescent(self):
        """Invariant check once idle: every block is back in the pool
        (prefix-cache parked blocks count — they are reclaimable), no
        standing reservations, no per-sequence tables. Chaos tests call
        this after driving fault schedules: any leak in a recovery path
        shows up here as missing blocks."""
        assert not self.has_work(), "engine still has work"
        self.kv.assert_quiescent()
        assert not self._adapter_pins, \
            f"adapter pin leak: {self._adapter_pins}"

    def _pr(self, req) -> np.ndarray:
        """Effective prompt: the resume form (original prompt + tokens
        generated before a preemption), the original prompt otherwise."""
        return req.prompt if req._resume is None else req._resume

    def _remaining(self, req) -> int:
        """max_new_tokens still to generate (tokens survive preemption)."""
        return req.max_new_tokens - len(req.tokens)

    def _worst_case_blocks(self, req) -> int:
        """Blocks a request can ever hold at once. Windowed models recycle
        below-window blocks, so the live span is bounded by the window
        (plus the write-frontier block) — but prefill scatters the WHOLE
        prompt before any recycling, so that is a floor.

        Beam requests (K slots): shared prompt blocks once, plus per beam
        the generated span (straddling ≤ ceil(new/bs)+1 blocks), plus 2
        per beam for the copy-on-write partial forks (one held, one
        transient while the new fork exists before the parent is freed)."""
        p = len(self._pr(req))
        if req.num_beams > 1:
            k = req.num_beams
            return (self.mgr.blocks_needed(p)
                    + k * (self.mgr.blocks_needed(
                        req.max_new_tokens + self.block_size) + 2))
        total = p + self._remaining(req)
        if self.window is None or self.mixed:   # the full space keeps all
            return self.mgr.blocks_needed(total)
        live = self.mgr.blocks_needed(
            min(total, self.window + 2 * self.block_size))
        return max(self.mgr.blocks_needed(p), live)

    # --------------------------------------- multi-LoRA / grammar state
    def _release_adapter(self, rid: int):
        """Drop the ref-count pin the scheduler took at admission (idempotent
        — every detach/finish/preempt path calls it)."""
        aid = self._adapter_pins.pop(rid, None)
        if aid is not None and self.adapter_store is not None:
            self.adapter_store.release(aid)

    def _req_aidx(self, req) -> int:
        """Cache index of the request's pinned adapter (-1 = base path).
        Pinned entries are never evicted, so the index is stable for the
        request's whole slot tenure."""
        if req.req_id in self._adapter_pins:
            return self.adapter_store.index_of(req.adapter_id)
        return -1

    def _lora_arg(self, aidx, width: int):
        """The per-row lora pytree ``models.paged._lora_delta`` consumes,
        or None when no row carries an adapter (the None path traces the
        exact base program — bit-exactness by construction). ``aidx``:
        per-row cache index (-1 = base); ``width``: padded tokens per row
        in the forward — rows are contiguous token spans after the
        perm+reshape, so group sizes are row-counts times width."""
        if self.adapter_store is None:
            return None
        aidx = np.asarray(aidx, np.int64)
        if not (aidx >= 0).any():
            return None
        cap = self.adapter_store.capacity
        order = np.argsort(np.where(aidx < 0, cap, aidx), kind="stable")
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order))
        gs = np.bincount(aidx[aidx >= 0], minlength=cap) * width
        lora = self.adapter_store.stacks()
        lora["perm"] = jnp.asarray(order, jnp.int32)
        lora["inv"] = jnp.asarray(inv, jnp.int32)
        lora["gs"] = jnp.asarray(gs, jnp.int32)
        return lora

    def _bind_grammar(self, slot: int, req):
        """(Re)bind a slot's grammar state at activation. The state is a
        pure function of the emitted tokens, so a resume or an install
        replays ``req.tokens`` — preemption cannot drift the mask."""
        if req.grammar is None:
            self._grammar.pop(slot, None)
            return
        st = req.grammar.start_state
        for t in req.tokens:
            st = req.grammar.advance(st, int(t))
        self._grammar[slot] = [req.grammar, st]

    def _grammar_bias_rows(self, rows_slots, n_rows: int):
        """[n_rows, V] logit bias (0 / -1e30) for the listed (row, slot)
        pairs; None when no listed slot is grammar-bound — the sampler
        then traces its unbiased program, bit-identical to pre-grammar."""
        bound = [(i, s) for i, s in rows_slots if s in self._grammar]
        if not bound:
            return None
        bias = np.zeros((n_rows, self.cfg.vocab_size), np.float32)
        for i, s in bound:
            aut, st = self._grammar[s]
            bias[i] = aut.bias(st)
        return bias

    # ---------------------------------------------------------- admission
    def _admit(self):
        return self.sched.select_admissions(self)

    def _admit_state(self, req, slot: int, match) -> bool:
        """The host's state work of one admission of a model with
        recurrent layers (the ``serving.state`` span, under
        ``serving.admit``). ``match`` is what ``KVManager.match`` offered:
        the K/V match cut down to its deepest snapshot. That snapshot is
        restored into the slot (a device copy, ordered before the slot's
        first chunk by its data). THE POLICY OF TAKING: where the K/V match
        reached past the snapshot, this prefix is being seen a second time,
        so the state at the end of the K/V match (block-aligned) is
        snapshotted when this request's prefill reaches it: an entry is
        reserved now, the least recently restored one leaving if the pool
        is full. A prompt nobody asks again never takes an entry. -> whether
        a snapshot is planned (the prompt then goes through the chunk
        program, which can stop at that depth)."""
        restored = match.token_count if match else 0
        offered = match.offered if match is not None else 0
        self.mgr.cache_stats["snap_offered_tokens"] += offered
        with _span("serving.state", rid=req.req_id, matched=offered,
                   restored=restored) as sp:
            if restored:
                self.exe.restore_state(slot, match.snapshot[1])
                self.mgr.restored_snapshot(match.snapshot[1])
            at = offered // self.block_size * self.block_size
            got = self.mgr.reserve_snapshot() if at > restored else None
            if got is not None:
                req._snapshot_plan = (at, got[0])
            sp.set(taken=int(got is not None),
                   evicted=int(bool(got and got[1])))
        return got is not None

    def _drop_snapshot_plan(self, req):
        """A request leaves its slot before its prefill reached the depth
        it was to snapshot: the reserved entry is free again."""
        plan, req._snapshot_plan = req._snapshot_plan, None
        if plan is not None:
            self.mgr.release_snapshot(plan[1])

    def _live_blocks(self, rid: int) -> int:
        return self.kv.live_blocks(rid)

    def _update_resv(self, rid: int):
        self.kv.update(rid)

    def _window_worst_case(self, req) -> int:
        """Window-space blocks a request can ever hold at once (two block
        spaces): its whole length if that is less than what a window
        layer reads plus one chunk."""
        return min(self.mgr.blocks_needed(len(self._pr(req))
                                          + self._remaining(req)),
                   self._window_row_blocks)

    def _recycle_window(self, slots):
        """Free blocks entirely below cur - window for the given slots —
        live blocks per sequence stay O(window) in the layers that have a
        window. Host-only: the paged kernel masks every position BELOW
        lens - window, so stale table entries pointing at recycled (even
        reused) blocks are never read."""
        for slot in slots:
            self._recycle_row(int(self.slot_req[slot]), int(self.cur[slot]))

    def _recycle_row(self, rid: int, cur: int):
        """``_recycle_window`` for one request with ``cur`` tokens in the
        cache: in the window space where the model has two, else in the
        one space (whose reservation gets the headroom back)."""
        dead = max(0, cur - self.window) // self.block_size
        freed = dead > 0 and self._wspace.free_prefix(rid, dead)
        if freed:
            _WINDOW_RECYCLED.inc(len(freed))
            if not self.mixed:
                self._update_resv(rid)

    def _req_sampling(self, req):
        """(temperature, top_p) a request's tokens are sampled with."""
        return (self.default_temp if req.temperature is None
                else req.temperature,
                self.default_top_p if req.top_p is None else req.top_p)

    @staticmethod
    def _count_sampler(temps) -> bool:
        """Count one sampler call by the branch ``_sample_rows`` takes
        for it: ``temps`` are the temperatures of the rows that run the
        call. -> whether it is the greedy one."""
        greedy = not (temps > 0).any()
        _SAMPLER_CALLS.inc(path="greedy" if greedy else "stochastic")
        return greedy

    def _send_prefill_rows(self, live, chunked: bool):
        """Send the rows that carry a prompt this tick to one of the two
        prefill programs, ``prefill_rows`` of them a call, and sample the
        first tokens asked for. ``live``: a ``(tokens, offset, slot, table,
        adapter index, sampling)`` for each row, ``sampling`` its ``(temp,
        top_p)`` where this call's last logit chooses the row's first
        token, else None. Every call is dispatched before the one fetch,
        so the device runs a call while the host stages the next.
        -> (each call's logits, each row's first token or None)."""
        R, cap = self.prefill_rows, self.max_prompt_len
        nb, max_b = self.mgr.num_blocks, self.max_blocks_per_seq
        logits, toks = [], []     # toks: (a call's first row, its tokens)
        for g0 in range(0, len(live), R):
            ids = np.zeros((R, cap), np.int32)
            lens = np.zeros(R, np.int32)
            offs = np.zeros(R, np.int32)
            slots = np.full(R, self.num_slots, np.int32)  # sentinel = drop
            rows = np.full((R, max_b), nb, np.int32)
            wrows = ((np.full((R, max_b), self.kv.window.num_blocks,
                              np.int32),) if self.mixed else ())
            row_aidx = np.full(R, -1, np.int64)
            row_temps = np.zeros(R, np.float32)
            row_tps = np.ones(R, np.float32)
            sampled = []
            for i, (tokens, off, slot, table, aidx, sampling) in enumerate(
                    live[g0:g0 + R]):
                ids[i, :len(tokens)] = tokens
                lens[i] = len(tokens)
                offs[i] = off
                slots[i] = slot
                rows[i, :len(table)] = table
                if self.mixed:
                    # the window space's table: recycled positions keep
                    # the sentinel (the kernels never read below a window)
                    for j, blk in enumerate(self.kv.window.tables[
                            int(self.slot_req[slot])]):
                        if blk is not None:
                            wrows[0][i, j] = blk
                row_aidx[i] = aidx
                if sampling is not None:
                    row_temps[i], row_tps[i] = sampling
                    sampled.append((i, slot))
            lora = self._lora_arg(row_aidx, cap)
            if chunked:
                out = self.exe.prefill_chunk(ids, lens, offs, slots, rows,
                                             lora=lora, wrows=wrows)
            else:
                out = self.exe.prefill(ids, lens, slots, rows, lora=lora,
                                       wrows=wrows)
            # roofline: one weight pass a call; a chunk attends its own
            # tokens plus everything already consumed (its offset)
            self._acc_phase("prefill", int(lens.sum()), 1,
                            self._ctx_causal(lens, offs))
            logits.append(out)
            if sampled:
                self._prefill_greedy.append(self._count_sampler(row_temps))
                toks.append((g0, self.exe.sample_rows(
                    out, row_temps, row_tps,
                    bias=self._grammar_bias_rows(sampled, R))))
        # the dead rows of the calls sent burned device FLOPs on no
        # request's behalf
        GOODPUT.waste("pad_rows", (R * len(logits) - len(live)) * cap)
        self._prefill_sent[0] += len(live)
        self._prefill_sent[1] += len(logits)
        first = [None] * len(live)
        if toks:
            for (g0, _), got in zip(toks, self.exe.fetch_sampled(
                    [t for _, t in toks])):
                n = min(R, len(live) - g0)
                first[g0:g0 + n] = got[:n].tolist()
        return logits, first

    def _prefill(self, admits, beam_admits=()):
        """The whole-prompt forward for every prompt admitted this tick:
        greedy prompts first, then each beam request's prompt as one more
        row (written into its beam-0 slot; the forks are installed after,
        in ``_beam_init``)."""
        if not admits and not beam_admits:
            return []
        live = []
        for slot, req in admits:
            p = self._pr(req)
            t = self.mgr.tables[req.req_id]
            self.slot_req[slot] = req.req_id
            self.active[slot] = True
            self.cur[slot] = len(p)
            self.gen[slot] = 0
            self.max_gen[slot] = self._remaining(req)
            self._adm_counter += 1
            self.adm_order[slot] = self._adm_counter
            self.table_len[slot] = len(t)
            self.temps[slot], self.top_ps[slot] = self._req_sampling(req)
            self.slot_aidx[slot] = self._req_aidx(req)
            self._bind_grammar(slot, req)
            # fresh draft state unless the resident draft cache covers a
            # radix-adopted prefix (an evicted slot's draft cache was
            # "freed" by zeroing this frontier — replay rebuilds it)
            self._seed_draft(slot, req)
            self.slot_k[slot] = self.spec_k
            self._acc_ema[slot] = 1.0
            REQUESTS.event(req, "prefill", replica=self.trace_name,
                           slot=slot, tokens=len(p))
            live.append((p, 0, slot, t, self.slot_aidx[slot],
                         (self.temps[slot], self.top_ps[slot])))
        n = len(admits)
        beams = []
        # every beam allocation lands before the first call is sent, so
        # the guard covers all of the tick's calls
        self._staged_admits = frozenset(r.req_id for _, r in admits)
        for bslots, req in beam_admits:
            g, grows, csrc, cdst = self._beam_alloc(bslots, req)
            live.append((req.prompt, 0, bslots[0], grows[0], -1, None))
            beams.append((g, grows, csrc, cdst))
        logits, first = self._send_prefill_rows(live, chunked=False)
        self._staged_admits = frozenset()   # scatter landed: evictable again
        if self.window is not None:
            # a long prompt's below-window blocks die the moment prefill
            # has scattered them — and from here on the sequence can never
            # hold more than the window live bound, so relax its
            # reservation too (the prompt-size floor only mattered DURING
            # prefill)
            self._recycle_window([slot for slot, _ in admits])
        if self.window is not None and not self.mixed:
            # (two spaces: the reservation is the full space's, which
            # keeps every block)
            live_bound = self.mgr.blocks_needed(
                self.window + 2 * self.block_size)
            for slot, req in admits:
                rid = req.req_id
                self.kv.need[rid] = min(self.kv.need[rid], live_bound)
                self._update_resv(rid)
        emitted = []
        for i, (slot, req) in enumerate(admits):
            emitted += self._emit(slot, first[i])
        R = self.prefill_rows
        for bi, (g, grows, csrc, cdst) in enumerate(beams):
            i = n + bi
            emitted += self._beam_init(g, grows, csrc, cdst,
                                       logits[i // R][i % R])
        return emitted

    # ------------------------------------------------------------ beams
    def _group_live_blocks(self, g: _BeamGroup) -> int:
        """Distinct pool blocks held by the whole group (shared prompt
        blocks appear in several beams' tables — count them once)."""
        return len({b for sid in g.sid.values()
                    for b in self.mgr.tables.get(sid, []) if b is not None})

    def _update_resv_group(self, rid: int):
        self.kv.update(rid, live=self._group_live_blocks(self.groups[rid]))

    def _new_sid(self, rid):
        self._sid_counter += 1
        return (rid, self._sid_counter)

    def _beam_alloc(self, slots, req: Request):
        """Host/manager phase of beam admission: allocate the prompt under
        beam 0's key and fork the other beams copy-on-write. Returns the
        group plus the fork data; the prompt itself rides as ONE row of
        the shared admission prefill."""
        k, s, rid = req.num_beams, len(req.prompt), req.req_id
        nb, max_b = self.mgr.num_blocks, self.max_blocks_per_seq
        g = _BeamGroup(req=req, slots=list(slots), s=s)
        g.sid = {j: self._new_sid(rid) for j in range(k)}
        # protect same-tick greedy admits: their prefill rows are staged
        # but the scatter hasn't run yet (this is called mid-_prefill)
        prot = self._staged_admits
        self._mgr_retry(self.mgr.allocate, g.sid[0], s, protect=prot)
        rows = np.full((k, max_b), nb, np.int32)
        copy_src = np.full(k, nb, np.int32)
        copy_dst = np.full(k, nb, np.int32)
        for j in range(1, k):
            pair = self._mgr_retry(self.mgr.fork, g.sid[0], g.sid[j], s,
                                   protect=prot)
            if pair is not None:
                copy_src[j], copy_dst[j] = pair
        for j in range(k):
            t = self.mgr.tables[g.sid[j]]
            rows[j, :len(t)] = t
        return g, rows, copy_src, copy_dst

    def _beam_init(self, g: _BeamGroup, rows, copy_src, copy_dst,
                   logits_row):
        """Device-state phase after the shared prefill: install the forked
        tables, init the selection state from the prompt's last logits,
        then run the group's FIRST select so its slots enter this tick's
        forward with real beam tokens."""
        req, s, rid, k = g.req, g.s, g.req.req_id, g.req.num_beams
        self.exe.beam_group_update(g.slots, rows, s, copy_src, copy_dst)
        neg = jnp.float32(-1e9)
        vocab = self.cfg.vocab_size
        logp0 = jax.nn.log_softmax(logits_row.astype(jnp.float32))
        g.logp = jnp.broadcast_to(logp0[None], (k, vocab))
        g.running_lp = jnp.asarray([0.0] + [float(neg)] * (k - 1),
                                   jnp.float32)
        max_len = s + req.max_new_tokens
        g.seqs = jnp.zeros((k, max_len), jnp.int32).at[:, :s].set(
            jnp.asarray(req.prompt)[None])
        g.fin_seqs = jnp.zeros_like(g.seqs)
        g.fin_scores = jnp.full((k,), neg, jnp.float32)

        for slot in g.slots:
            self.slot_req[slot] = rid
            self.active[slot] = True
            self.is_beam[slot] = True
            self.cur[slot] = s
            self.temps[slot] = 0.0       # beam tokens come from select
            self.top_ps[slot] = 1.0
        self.groups[rid] = g
        self._update_resv_group(rid)
        return self._beam_advance(rid, g)

    def _beam_advance(self, rid: int, g: _BeamGroup):
        """One beam select over the group's pending logp; fork the caches
        along the chosen parents (or finalize at the last select).
        Selection/fork math mirrors ``paged_beam_search`` exactly."""
        k = g.req.num_beams
        (g.running_lp, g.seqs, g.fin_seqs, g.fin_scores, new_beam,
         new_tok) = _BEAM_SELECT_JIT(
            g.running_lp, g.seqs, g.fin_seqs, g.fin_scores, g.logp,
            jnp.int32(g.i), g.s, self.eos_token_id,
            float(g.req.length_penalty))
        if g.i == g.req.max_new_tokens - 1:
            return self._finalize_beam(rid, g)
        parents = np.asarray(new_beam)
        toks = np.asarray(new_tok)
        cur = g.s + g.i                       # tokens stored per beam
        nb, max_b = self.mgr.num_blocks, self.max_blocks_per_seq
        rows = np.full((k, max_b), nb, np.int32)
        copy_src = np.full(k, nb, np.int32)
        copy_dst = np.full(k, nb, np.int32)
        new_sids = {}
        for j in range(k):
            dst = self._new_sid(rid)
            pair = self._mgr_retry(self.mgr.fork,
                                   g.sid[int(parents[j])], dst, cur)
            if pair is not None:
                copy_src[j], copy_dst[j] = pair
            new_sids[j] = dst
        for j in range(k):
            self.mgr.free(g.sid[j])
        g.sid = new_sids
        for j in range(k):
            t = self._mgr_retry(                      # room for the write
                self.mgr.allocate, g.sid[j], cur + 1)
            rows[j, :len(t)] = t
        self.exe.beam_group_update(g.slots, rows, cur, copy_src, copy_dst)
        self._update_resv_group(rid)
        for j, slot in enumerate(g.slots):
            self.last_tok[slot] = toks[j]
        g.i += 1
        return []

    def _finalize_beam(self, rid: int, g: _BeamGroup):
        req = g.req
        best_seq, best_score = _beam_finalize(
            g.running_lp, g.seqs, g.fin_seqs, g.fin_scores, g.s,
            req.max_new_tokens, self.eos_token_id,
            float(req.length_penalty))
        req.tokens = [int(t) for t in np.asarray(best_seq)[g.s:]]
        req.beam_score = float(best_score)
        req.done = True
        req.finish_reason = "beam"
        _FINISHED.inc(reason="beam")
        if req.tenant_id is not None:
            _TENANT_FINISHED.inc(tenant=tenant_label(req.tenant_id),
                                 reason="beam")
        _TOKENS.inc(len(req.tokens))
        GOODPUT.good(len(req.tokens), tenant=req.tenant_id)
        REQUESTS.tokens(req, len(req.tokens))
        REQUESTS.event(req, "kv_peak", replica=self.trace_name,
                       blocks=sum(self.kv.take_peak(s)
                                  for s in g.sid.values()))
        REQUESTS.finish(req, "beam", replica=self.trace_name)
        for sid in g.sid.values():
            self.mgr.free(sid)
        for slot in g.slots:
            self.active[slot] = False
            self.is_beam[slot] = False
            self.slot_req[slot] = -1
        self.kv.release(rid)
        del self.groups[rid]
        return [(rid, t) for t in req.tokens]

    def _prefill_chunks(self):
        """One chunk (≤ max_prompt_len tokens) for every in-flight
        chunked prefill — vLLM-style: long prompts stream in across
        ticks while other slots keep decoding. The final chunk samples
        the request's first token and activates its slot."""
        self._apply_prefix_copies()
        if not self.prefilling:
            return []
        cap = self.max_prompt_len
        # ladder L2: shrink the per-tick chunk budget, not the jitted
        # geometry — the ids array keeps its (prefill_rows, cap) shape
        # (lens just come up shorter), so degrading never recompiles
        budget = (cap if self.degrade is None
                  else min(cap, self.degrade.prefill_budget(cap)))
        live, rids = [], []
        # every row's blocks are allocated before the first call is sent:
        # rows already staged (their KV scatter is pending) are protected
        # from a later row's preemption, across all of the tick's calls
        staged = set()
        for rid, (slot, consumed) in list(self.prefilling.items()):
            if rid not in self.prefilling:   # evicted by an earlier row's
                continue                     # allocation this tick
            req = self.requests[rid]
            p = self._pr(req)
            chunk = p[consumed: consumed + budget]
            plan = req._snapshot_plan
            if plan is not None and consumed < plan[0] < consumed + len(chunk):
                # the chunk stops at the depth to snapshot: the slot's
                # state after the call is the state at that depth
                chunk = chunk[:plan[0] - consumed]
            t = self._allocate_or_preempt(rid, consumed + len(chunk),
                                          protect=staged)
            if t is None:
                continue         # no blocks this tick: row stays queued
            staged.add(rid)
            self._update_resv(rid)
            REQUESTS.event(req, "prefill_chunk", replica=self.trace_name,
                           slot=slot, offset=consumed, tokens=len(chunk))
            sampling = None
            if consumed + len(chunk) >= len(p):
                # the last chunk: bind grammar BEFORE the first-token
                # sample so the mask bias covers it (state replays
                # req.tokens for resumes)
                self._bind_grammar(slot, req)
                sampling = self._req_sampling(req)
            live.append((chunk, consumed, slot, t, self._req_aidx(req),
                         sampling))
            rids.append(rid)
        if not live and not self.active.any() and not self.groups:
            # nothing decoded this tick and no prefill row got blocks even
            # though preemption could evict every OTHER prefill: the pool
            # cannot fit one chunk of the sole remaining request — no
            # future tick can differ, so raise instead of spinning
            FLIGHT.record("serving.alloc_fail",
                          rids=[int(r) for r in self.prefilling],
                          **self.kv.ledger.flight_fields())
            FLIGHT.dump(reason="kv_alloc_fail")
            raise MemoryError(
                "paged pool cannot fit one prefill chunk of the remaining "
                "request(s) even after preemption — increase num_blocks or "
                "reduce max_prompt_len (chunk size)")
        if not live:
            # every prefilling row is starved of blocks this tick (decode
            # keeps the engine alive): nothing to scatter, no call
            return []
        _, first = self._send_prefill_rows(live, chunked=True)
        emitted = []
        for rid, (chunk, consumed, slot, _, _, sampling), tok in zip(
                rids, live, first):
            req = self.requests[rid]
            plan = req._snapshot_plan
            if plan is not None and consumed + len(chunk) == plan[0]:
                # queued behind the chunk's call: the entry holds the state
                # at this depth, and the trie position there owns it
                self.exe.take_state(slot, plan[1])
                req._snapshot_plan = None
                self.mgr.attach_snapshot(self._pr(req), plan[0], plan[1],
                                         adapter=req.adapter_id)
            if self.window is not None:
                # the chunk is queued: the window layers' rows below the
                # next chunk's first window are dead (two block spaces;
                # a one-space windowed model sends no chunk)
                self._recycle_row(rid, consumed + len(chunk))
            if sampling is None:
                self.prefilling[rid] = (slot, consumed + len(chunk))
                continue
            del self.prefilling[rid]
            p = self._pr(req)
            if self.prefix_caching:
                self.mgr.commit_prefix(rid, p, adapter=req.adapter_id)
            self.active[slot] = True
            self.cur[slot] = len(p)
            self.gen[slot] = 0
            self.max_gen[slot] = self._remaining(req)
            self._adm_counter += 1
            self.adm_order[slot] = self._adm_counter
            self.table_len[slot] = len(self.mgr.tables[rid])
            self.temps[slot], self.top_ps[slot] = sampling
            self.slot_aidx[slot] = self._req_aidx(req)
            # cached/long prompts land here — the site where a radix
            # adoption can seed the draft frontier from resident K/V
            self._seed_draft(slot, req)
            self.slot_k[slot] = self.spec_k
            self._acc_ema[slot] = 1.0
            emitted += self._emit(slot, tok)
        return emitted

    def _apply_prefix_copies(self):
        """Drain the radix manager's host-side COW plan (partial boundary
        blocks adopted at admission) into ONE device copy. Runs before
        any other program of the tick writes the pool, so jax data
        dependencies order the copy ahead of the adopters' prefill
        chunks and ahead of any reallocation of a source block."""
        take = getattr(self.mgr, "take_copy_plan", None)
        if take is None:
            return
        pairs = take()
        if pairs:
            self.exe.apply_block_copies(pairs)

    # --------------------------------------------------------- preemption
    def _preempt(self, protect_rid=None) -> bool:
        # preemption rewrites a victim's resume prompt from req.tokens —
        # tokens still in flight in the async window must land first or
        # the replayed stream would silently drop them
        return self._preempted(self.sched.preempt, protect_rid)

    _protect = staticmethod(Scheduler._protect)

    def _preempt_prefilling(self, protect_rid=None) -> bool:
        return self._preempted(self.sched.preempt_prefilling, protect_rid)

    def _preempt_from(self, cand) -> bool:
        return self._preempted(self.sched.preempt_from, cand)

    def _preempted(self, preempt, arg) -> bool:
        self._drain_async("boundary")
        done = preempt(self, arg)
        self._gauge_force |= done    # a victim left its slot: exact sweep
        return done

    def _allocate_or_preempt(self, rid: int, n_tokens: int, protect=None):
        """mgr.allocate with out-of-blocks recovery: preempt greedy slots
        (never ``rid`` itself, nor anything in ``protect`` — rows already
        staged into this tick's jitted batch) until the allocation fits.
        Returns the table, or None when preemption is off / nothing could
        be freed (caller skips this row for the tick — progress resumes
        when blocks free up).

        Respects OTHER requests' standing reservations: a greedy request
        (which carries none under preemption) must preempt before dipping
        into blocks a beam group's worst-case reservation counts on —
        otherwise a later beam select can raise MemoryError out of
        ``step()`` mid-update, corrupting engine state."""
        protect = self._protect(protect) | {rid}
        while True:
            others = self._reserved - self._resv.get(rid, 0)
            # need mirrors mgr.allocate: table POSITIONS — including the
            # None placeholders window recycling leaves — already cover
            # their token span; counting only live blocks would inflate
            # need without bound as a windowed sequence recycles
            # (spurious preemption storm, then a crash)
            need = (self.mgr.blocks_needed(n_tokens)
                    - len(self.mgr.tables.get(rid, [])))
            try:
                # chaos hook: an injected MemoryError exercises the same
                # preempt-and-retry recovery a genuinely dry pool would
                fault_point("serving.alloc", rid=rid, engine=self)
                if need > self.mgr.free_blocks - max(0, others):
                    raise MemoryError("allocation would dip into blocks "
                                      "reserved by other requests")
                return self.mgr.allocate(rid, n_tokens)
            except MemoryError:
                if not self.preemption or not self._preempt(
                        protect_rid=protect):
                    if self.preemption:
                        return None
                    # hard failure escapes step(): leave the ledger's view
                    # of who holds the missing blocks in the flight ring
                    FLIGHT.record("serving.alloc_fail", rid=int(rid),
                                  **self.kv.ledger.flight_fields())
                    raise

    def _mgr_retry(self, fn, *a, protect=None):
        """Beam-group block growth with out-of-blocks recovery: route
        through greedy preemption instead of letting MemoryError escape
        ``step()`` mid-cache-update. The group's worst-case reservation
        (+2 transient fork blocks per beam) should make this unreachable
        now that greedy growth respects reservations; this is the
        belt-and-braces path. ``protect``: req_ids whose prefill rows are
        staged but not yet scattered (evicting one would corrupt the KV
        writes about to land)."""
        while True:
            try:
                return fn(*a)
            except MemoryError:
                if not self.preemption or not self._preempt(
                        protect_rid=protect):
                    raise

    # ------------------------------------------------- speculative decode
    def _spec_probs(self, logits_row, temp, top_p):
        """Host mirror of ``decoding._sample_rows``'s filtered target
        distribution for one row (temperature > 0): temperature scale →
        static top_k cut → nucleus (top_p) cut → renormalise. The accept
        rule must compare proposals against EXACTLY the distribution the
        non-spec tick samples from, or speculation would change the
        output law."""
        scaled = np.asarray(logits_row, np.float64) / temp
        if self.top_k is not None and self.top_k > 0:
            kth = np.sort(scaled)[-self.top_k]
            scaled = np.where(scaled < kth, -1e30, scaled)
        srt = np.sort(scaled)[::-1]
        e = np.exp(srt - srt[0])
        cum = np.cumsum(e / e.sum())
        cutoff = srt[int((cum < top_p).sum())]
        scaled = np.where(scaled < cutoff, -1e30, scaled)
        e = np.exp(scaled - scaled.max())
        return e / e.sum()

    def _committed_seq(self, slot: int) -> np.ndarray:
        """The slot's committed sequence: effective prompt + tokens
        generated SINCE activation (earlier generations are already baked
        into the resume prompt). Its last token is ``last_tok`` — sampled
        but not yet written to the target cache — so len == cur + 1."""
        req = self.requests[int(self.slot_req[slot])]
        g = int(self.gen[slot])
        toks = np.asarray(req.tokens[len(req.tokens) - g:], np.int32)
        return np.concatenate([self._pr(req), toks])

    def _seed_draft(self, slot: int, req):
        """Seed a freshly activated slot's draft frontier from the
        resident draft cache (ISSUE 11, closing PR 9's REMAINING). The
        dense draft cache is per-slot and nothing writes it while the
        slot is parked, so rows 0..len(resident)-1 still hold the draft
        K/V of the previous session's committed prefix. When the new
        request radix-adopted a prefix that matches those resident ids,
        the adopted span's draft-side re-prefill is pure replay — skip
        it by advancing ``draft_cur`` past the match. The reuse window
        is capped at the adopted span: only radix-adopted tokens were
        ever drafted before, and the accept rule preserves the target
        law for ANY draft state, so a conservative cap costs nothing in
        correctness."""
        p = self._pr(req)
        adopted = int(getattr(req, "_adopted", 0))
        self._adopted_span[slot] = min(adopted, len(p))
        reuse = 0
        if adopted > 0 and self.exe.draft_model is not None:
            res = self._draft_resident.get(slot)
            if res is not None and len(res):
                # cap below len(p): the steady feed needs >= 1 pending
                # token so its last logit can seed the first proposal
                m = min(len(res), adopted, len(p) - 1)
                if m > 0:
                    eq = np.asarray(res[:m]) == np.asarray(p[:m])
                    reuse = int(m if eq.all() else np.argmin(eq))
        self.draft_cur[slot] = reuse
        if reuse:
            GOODPUT.saved(reuse, tenant=req.tenant_id)
            _SPEC_DRAFT_REUSE.inc(reuse)

    def _spec_draft(self, staged, seqs):
        """Draft phase: catch each staged slot's draft cache up to its
        committed frontier (chunked, for freshly admitted/replayed slots
        whose draft cache is empty), then autoregressively propose up to
        k_eff tokens per slot. Returns (props, qs) keyed by slot; qs[slot]
        is None for greedy rows, else the per-proposal draft
        distributions the accept rule needs."""
        ns = self.num_slots
        kmax = max(k for _, _, k in staged)
        Cs = self.spec_k + 1

        # ---- catch-up: wide chunks until every pending suffix fits the
        # steady feed (pending >= 1 always — last_tok is never in cache)
        CH = max(self.max_prompt_len, Cs)
        while True:
            pend_len = {s: len(seqs[s]) - int(self.draft_cur[s])
                        for s, _, _ in staged}
            if max(pend_len.values()) <= Cs:
                break
            ids = np.zeros((ns, CH), np.int32)
            cl = np.zeros(ns, np.int32)
            rp = np.zeros(ns, np.int32)
            for s, rid, _ in staged:
                if pend_len[s] <= Cs:
                    continue               # already caught up: no writes
                n = min(pend_len[s] - 1, CH)   # keep >= 1 for the steady feed
                dc = int(self.draft_cur[s])
                ids[s, :n] = seqs[s][dc: dc + n]
                cl[s] = n
                rp[s] = dc
                # re-embedding inside the radix-adopted span is pure
                # replay (first-time prompt embedding is not waste)
                GOODPUT.waste("replay_prefill",
                              min(dc + n, int(self._adopted_span[s])) - dc,
                              tenant=getattr(self.requests.get(rid),
                                             "tenant_id", None))
            self.exe.draft_rows(ids, rp, cl)
            self._acc_phase("spec_draft", int(cl.sum()), 1,
                            self._ctx_causal(cl, rp))
            for s, _, _ in staged:
                self.draft_cur[s] += int(cl[s])

        # ---- steady feed: the pending suffix (<= k+1 tokens) in one
        # fixed-width chunk; its last logit seeds the first proposal
        ids = np.zeros((ns, Cs), np.int32)
        cl = np.zeros(ns, np.int32)
        rp = np.zeros(ns, np.int32)
        for s, rid, _ in staged:
            dc = int(self.draft_cur[s])
            pend = seqs[s][dc:]
            ids[s, :len(pend)] = pend
            cl[s] = len(pend)
            rp[s] = dc
            GOODPUT.waste("replay_prefill",
                          min(dc + len(pend),
                              int(self._adopted_span[s])) - dc,
                          tenant=getattr(self.requests.get(rid),
                                         "tenant_id", None))
        dl = self.exe.draft_rows(ids, rp, cl)
        self._acc_phase("spec_draft", int(cl.sum()), 1,
                        self._ctx_causal(cl, rp))
        for s, _, _ in staged:
            self.draft_cur[s] += int(cl[s])      # == cur + 1 now
        dlast = jnp.take_along_axis(
            dl, jnp.maximum(jnp.asarray(cl, jnp.int32) - 1,
                            0)[:, None, None], axis=1)[:, 0]

        props = {s: [] for s, _, _ in staged}
        qs = {s: (None if float(self.temps[s]) == 0.0 else [])
              for s, _, _ in staged}

        def pick(slot, row):
            temp = float(self.temps[slot])
            if temp == 0.0:
                return int(np.argmax(row))
            z = np.asarray(row, np.float64) / temp
            e = np.exp(z - z.max())
            q = e / e.sum()
            qs[slot].append(q)
            return int(self._spec_rs.choice(q.size, p=q))

        def pick_all(logits_2d, rows_feeding):
            ng = [s for s in rows_feeding if float(self.temps[s]) != 0.0]
            greedy = [s for s in rows_feeding
                      if float(self.temps[s]) == 0.0]
            if greedy:           # fetch [ns] ints, never the [ns, V] block
                am = np.asarray(jnp.argmax(
                    logits_2d.astype(jnp.float32), axis=-1))
                self._spec_fetch_bytes += am.nbytes
                for s in greedy:
                    props[s].append(int(am[s]))
            if ng:
                # gather ONLY the non-greedy rows on device before the
                # host fetch — one temperature slot no longer taxes every
                # greedy slot's D2H with the full [ns, V] block
                sub = np.asarray(
                    logits_2d[jnp.asarray(ng)].astype(jnp.float32))
                self._spec_fetch_bytes += sub.nbytes
                for i, s in enumerate(ng):
                    props[s].append(pick(s, sub[i]))

        pick_all(dlast, [s for s, _, _ in staged])
        # ---- autoregressive proposal rounds (single-token feeds)
        for r in range(1, kmax):
            feeding = [s for s, _, k in staged if k > r]
            if not feeding:
                break
            ids1 = np.zeros((ns, 1), np.int32)
            cl1 = np.zeros(ns, np.int32)
            rp1 = np.zeros(ns, np.int32)
            for s in feeding:
                ids1[s, 0] = props[s][-1]
                cl1[s] = 1
                rp1[s] = int(self.draft_cur[s])
            dl1 = self.exe.draft_rows(ids1, rp1, cl1)
            self._acc_phase("spec_draft", int(cl1.sum()), 1,
                            self._ctx_causal(cl1, rp1))
            for s in feeding:
                self.draft_cur[s] += 1           # == cur + r + 1
            pick_all(dl1[:, 0], feeding)
        return props, qs

    def _spec_tick(self, elig):
        """One draft-and-verify round for the eligible slots. Returns
        (handled mask, emitted): handled slots advanced up to k_eff+1
        tokens and skip this tick's one-token path.

        Staging allocates verify coverage (cur + k_eff + 1 tokens) per
        slot BEFORE any device work, protecting already-staged rows from
        preemption — mirrors ``_prefill_chunks``. The ``serving.spec_verify``
        fault point fires before the donating verify jit, so an injected
        exception aborts with the cache, tables, and ledgers exactly as
        the staging left them (staged blocks live in request tables — the
        normal free path reclaims them) and the tick falls back to
        one-token decode for every slot."""
        handled = np.zeros(self.num_slots, bool)
        emitted: list = []
        ns = self.num_slots
        # ---- stage: clamp k, allocate coverage for the worst case ----
        staged = []                        # (slot, rid, k_eff)
        staged_rids: set = set()
        for slot in np.nonzero(elig)[0]:
            slot = int(slot)
            if not self.active[slot]:
                continue                   # evicted by an earlier staging
            rid = int(self.slot_req[slot])
            k_cap = int(self.slot_k[slot]) if self.spec_adaptive \
                else self.spec_k
            k_eff = min(k_cap, int(self.max_gen[slot] - self.gen[slot]) - 1)
            if k_eff < 1:
                continue
            t = self._allocate_or_preempt(
                rid, int(self.cur[slot]) + k_eff + 1, protect=staged_rids)
            if t is None:
                continue                   # dry pool: one-token path today
            self._update_resv(rid)
            self.table_len[slot] = len(t)
            staged.append((slot, rid, k_eff))
            staged_rids.add(rid)
        staged = [(s, r, k) for s, r, k in staged if self.active[s]]
        if not staged:
            return handled, emitted

        seqs = {s: self._committed_seq(s) for s, _, _ in staged}
        with self._tick_timer("draft", "serving.draft", slots=len(staged)):
            props, qs = self._spec_draft(staged, seqs)

        # ---- verify: ONE batched target chunk over (slots, k_eff+1) ----
        C = self.spec_k + 1
        ids = np.zeros((ns, C), np.int32)
        clens = np.zeros(ns, np.int32)
        offs = np.zeros(ns, np.int32)
        slot_ids = np.full(ns, ns, np.int32)
        rows = np.full((ns, self.max_blocks_per_seq), self.mgr.num_blocks,
                       np.int32)
        v_aidx = np.full(ns, -1, np.int64)
        for slot, rid, k_eff in staged:
            ids[slot, 0] = self.last_tok[slot]
            ids[slot, 1: 1 + k_eff] = props[slot][:k_eff]
            clens[slot] = k_eff + 1
            offs[slot] = self.cur[slot]
            slot_ids[slot] = slot
            t = self.mgr.tables[rid]
            rows[slot, :len(t)] = t
            v_aidx[slot] = self.slot_aidx[slot]
        try:
            # chaos hook BEFORE the donating jit: an exception here must
            # leave self.cache intact (exception atomicity) — after the
            # donation there is no cache to fall back to
            fault_point("serving.spec_verify", engine=self,
                        slots=[s for s, _, _ in staged])
        except Exception as e:
            self.stats["spec_fallbacks"] += 1
            _SPEC_FALLBACKS.inc()
            FLIGHT.record("serving.spec_fallback",
                          error=f"{type(e).__name__}: {e}")
            # every drafted token of this round was burned unverified
            # (charged per slot so the metering ledger bills the tenant
            # whose draft burned, not __system__)
            for _, rid, k_eff in staged:
                GOODPUT.waste("chaos_abort", k_eff,
                              tenant=getattr(self.requests.get(rid),
                                             "tenant_id", None))
            # draft frontiers ran ahead of the commit that never came;
            # roll them back so the next round re-feeds from the frontier
            for slot, _, _ in staged:
                self.draft_cur[slot] = min(int(self.draft_cur[slot]),
                                           int(self.cur[slot]) + 1)
                # the rolled-back frontier still covers the committed
                # prefix: keep the resident snapshot coherent for reuse
                self._draft_resident[slot] = np.asarray(
                    seqs[slot][:int(self.draft_cur[slot])], np.int32)
                # staging extended the HOST table, but only the verify jit
                # would have installed those entries in the DEVICE row —
                # roll table_len back to what the device actually covers
                # so _grow_tables re-emits the missing entries; a later
                # spec round is self-healing (verify gets the full row)
                self.table_len[slot] = -(-int(self.cur[slot])
                                         // self.block_size)
            return np.zeros(self.num_slots, bool), []
        t_dev = time.perf_counter()
        with self._tick_timer("verify", "serving.verify",
                              slots=len(staged)):
            logits = np.asarray(self.exe.verify_chunk(
                ids, clens, offs, slot_ids, rows,
                lora=self._lora_arg(v_aidx, C)).astype(jnp.float32))
        self.stats["device_s"] += time.perf_counter() - t_dev
        # whole sentinel rows of the fixed-shape verify batch are waste
        GOODPUT.waste("pad_rows", (ns - len(staged)) * C)
        # roofline: one target weight pass; each verify row attends its
        # k_eff+1 chunk tokens plus the committed context at its offset
        self._acc_phase("spec_verify", int(clens.sum()), 1,
                        self._ctx_causal(clens, offs))

        # ---- accept/commit per slot; ONE batched length rewind after ----
        rw_slots = np.full(ns, ns, np.int32)
        rw_lens = np.zeros(ns, np.int32)
        for slot, rid, k_eff in staged:
            temp = float(self.temps[slot])
            row = logits[slot]                        # [C, V]
            # grammar slots: reject mask-violating drafts BEFORE the
            # accept law ever sees them (k_use truncates at the first
            # illegal proposal), then bias each verify position with the
            # mask of the state reached by accepting the proposals ahead
            # of it — the accept rule compares against EXACTLY the
            # masked distribution the non-spec tick samples from, so
            # speculation cannot change the constrained output law
            g = self._grammar.get(slot)
            gb, k_use = None, k_eff
            if g is not None:
                aut, st = g[0], g[1]
                gb, k_use = [], 0
                for i in range(k_eff):
                    b = aut.bias(st)
                    gb.append(b)
                    t_i = int(props[slot][i])
                    if b[t_i] != 0.0:
                        _GRAMMAR_SPEC_REJECTS.inc(k_eff - i)
                        break
                    st = aut.advance(st, t_i)
                    k_use += 1
                if k_use == k_eff:
                    gb.append(aut.bias(st))   # the bonus position's mask
            if temp == 0.0:
                vrow = (row[: k_use + 1] if gb is None
                        else row[: k_use + 1] + np.asarray(gb, np.float32))
                vs = vrow.argmax(axis=-1)
                n_acc = int(greedy_accept_length(vs[:k_use],
                                                 props[slot][:k_use]))
                new = [int(x) for x in props[slot][:n_acc]] \
                    + [int(vs[n_acc])]
            else:
                ps = [self._spec_probs(
                          row[i] if gb is None else row[i] + gb[i],
                          temp, float(self.top_ps[slot]))
                      for i in range(k_use + 1)]
                new, n_acc = stochastic_accept_row(
                    props[slot][:k_use], qs[slot], ps, self._spec_rs)
            cur0 = int(self.cur[slot])
            cur1 = cur0 + n_acc + 1
            self.cur[slot] = cur1
            rw_slots[slot] = slot
            rw_lens[slot] = cur1
            # draft frontier rolls back past rejected positions (stale
            # entries are overwritten by the next round's feed)
            self.draft_cur[slot] = min(int(self.draft_cur[slot]), cur1)
            # snapshot the token ids the draft cache now holds at
            # 0..draft_cur-1 — the reuse seed for this slot's NEXT
            # session (rows 0..draft_cur-1 always hold the committed
            # prefix after the rollback above)
            self._draft_resident[slot] = np.asarray(
                np.concatenate([seqs[slot], np.asarray(new, np.int32)])
                [:int(self.draft_cur[slot])], np.int32)
            if self.spec_adaptive:
                self._acc_ema[slot] = (0.5 * self._acc_ema[slot]
                                       + 0.5 * (n_acc / k_eff))
                self.slot_k[slot] = int(np.clip(
                    round(self._acc_ema[slot] * self.spec_k), 1,
                    self.spec_k))
            self.stats["spec_proposed"] += k_eff
            self.stats["spec_accepted"] += n_acc
            _SPEC_PROPOSED.inc(k_eff)
            _SPEC_ACCEPTED.inc(n_acc)
            _SPEC_TOKENS.observe(len(new))
            GOODPUT.waste("spec_rejected", k_eff - n_acc,
                          tenant=getattr(self.requests.get(rid),
                                         "tenant_id", None))
            REQUESTS.spec(self.requests.get(rid), k_eff, n_acc)
            handled[slot] = True
            for tok in new:
                emitted += self._emit(slot, int(tok))
                if self.slot_req[slot] < 0:
                    break      # EOS/length finished the request mid-list:
                    #            the rest of the accepted tokens is moot
        if self.stats["spec_proposed"]:
            _SPEC_RATE.set(self.stats["spec_accepted"]
                           / self.stats["spec_proposed"])
        # one rewind for all staged rows: length pointers only — verify
        # wrote k_eff+1 positions, the commit kept n_acc+1 of them
        self.exe.rewind_lens(rw_slots, rw_lens)
        self.stats["spec_ticks"] += 1
        return handled, emitted

    # ------------------------------------------------------------- decode
    def _grow_tables(self, mask=None):
        """At most one new block per slot per tick; returns the incremental
        (rows, cols, vals) update triple (sentinel-padded, fixed shape) and
        ``wvals``: ``(the window table's new entries,)`` for a model with
        two block spaces, () for any other.
        ``mask`` restricts growth to those slots (spec-handled slots skip
        the normal tick, so their updates must not ride a tick that may
        never run — their tables grow in the verify staging instead)."""
        rows = np.full(self.num_slots, self.num_slots, np.int32)
        cols = np.zeros(self.num_slots, np.int32)
        vals = np.zeros(self.num_slots, np.int32)
        # two block spaces: the window table grows at the same rows and
        # columns (both are indexed by position), by its own block numbers
        wvals = (np.zeros(self.num_slots, np.int32),) if self.mixed else ()
        base = (self.active & ~self.is_beam) if mask is None else mask
        crossing = base & (
            self.cur // self.block_size >= self.table_len)
        for slot in np.nonzero(crossing)[0]:     # ≤ once per bs ticks/slot
            if not self.active[slot]:
                continue                 # preempted earlier in this loop
            rid = int(self.slot_req[slot])
            t = self._allocate_or_preempt(rid, int(self.cur[slot]) + 1)
            if t is None:
                # nothing else to evict: preempt THIS slot (it re-queues
                # with its progress and resumes when blocks free up)
                if not self._preempt_from([int(slot)]):
                    raise MemoryError(
                        "paged cache out of blocks and the growing slot "
                        "is not preemptible (windowed/dynamic-rope resume "
                        "exceeds max_prompt_len)")
                continue
            self._update_resv(rid)
            # install the next entry the DEVICE row is missing — normally
            # the block just allocated (table_len == len(t)-1), but after
            # a spec-verify fallback the host table can be ahead by more
            # than one staged-but-never-installed block
            idx = min(int(self.table_len[slot]), len(t) - 1)
            rows[slot] = slot
            cols[slot] = idx
            vals[slot] = t[idx]
            if self.mixed:
                wvals[0][slot] = self.kv.window.tables[rid][idx]
            self.table_len[slot] = idx + 1
        if self.window is not None:
            self._recycle_window(np.nonzero(self.active & ~self.is_beam)[0])
        return rows, cols, vals, wvals

    def _emit(self, slot: int, token: int):
        """Record one sampled token for the request in ``slot``; finish on
        EOS or length. Returns [(req_id, token)]."""
        rid = int(self.slot_req[slot])
        if rid < 0:
            return []        # slot emptied mid-tick (stream-side cancel)
        req = self.requests[rid]
        req.tokens.append(token)
        _TOKENS.inc()
        GOODPUT.good(1, tenant=req.tenant_id)
        if req.tenant_id is not None:
            _TENANT_TOKENS.inc(tenant=tenant_label(req.tenant_id))
        g = self._grammar.get(slot)
        if g is not None:
            # advance the mask state past the committed token (EOS keeps
            # the state; an illegal token here would be a sampler bug and
            # raises loudly rather than derail the automaton silently)
            g[1] = g[0].advance(g[1], token)
            _GRAMMAR_TOKENS.inc()
        now = self._clock()
        if req._first_tok_t is None:
            req._first_tok_t = now
            if req._submit_t is not None:
                _TTFT.observe(max(0.0, now - req._submit_t))
                if req.tenant_id is not None:
                    _TENANT_TTFT.observe(
                        max(0.0, now - req._submit_t),
                        tenant=tenant_label(req.tenant_id))
            REQUESTS.event(req, "first_token", replica=self.trace_name,
                           slot=slot)
        elif req._last_tok_t is not None:
            _TOK_LAT.observe(max(0.0, now - req._last_tok_t))
            if req.tenant_id is not None:
                _TENANT_TOK_LAT.observe(
                    max(0.0, now - req._last_tok_t),
                    tenant=tenant_label(req.tenant_id))
        req._last_tok_t = now
        if req.stream is not None:
            req.stream(req, token)
        self.last_tok[slot] = token
        self.gen[slot] += 1
        REQUESTS.tokens(req)
        eos = self.eos_token_id is not None and token == self.eos_token_id
        if eos or self.gen[slot] >= self.max_gen[slot]:
            req.done = True
            req.finish_reason = "eos" if eos else "length"
            self._gauge_force = True     # finish boundary: exact sweep
            _FINISHED.inc(reason=req.finish_reason)
            if req.tenant_id is not None:
                _TENANT_FINISHED.inc(tenant=tenant_label(req.tenant_id),
                                     reason=req.finish_reason)
            if self.prefix_caching:
                # commit the GENERATED span too before the blocks park —
                # decode output becomes matchable (multi-turn chat
                # re-submits prompt+answer as the next prompt). Commit
                # only up to the cache frontier ``cur``: the token just
                # sampled has no KV scattered yet
                seq = np.concatenate([req.prompt,
                                      np.asarray(req.tokens, np.int32)])
                self.mgr.commit_prefix(
                    rid, seq[:min(len(seq), int(self.cur[slot]))],
                    adapter=req.adapter_id)
            self.mgr.free(rid)
            self.kv.release(rid)
            self.active[slot] = False
            self.slot_req[slot] = -1
            self.slot_aidx[slot] = -1
            self._grammar.pop(slot, None)
            self._release_adapter(rid)
            REQUESTS.event(req, "kv_peak", replica=self.trace_name,
                           blocks=self.kv.take_peak(rid))
            REQUESTS.finish(req, req.finish_reason,
                            replica=self.trace_name)
        return [(rid, token)]

    # -------------------------------------------- KV handoff (ISSUE 7)
    def extract_sequence(self, rid: int) -> KVPayload:
        """Lift a prefilled/decoding greedy sequence OUT of this engine:
        gather its KV blocks into a dense payload, then free the slot,
        blocks, and ledger entry. The request leaves with its tokens; the
        payload carries everything a decode replica needs to continue
        bit-exactly (``install_sequence``). Raises for beam/chunk-mid
        requests — only ACTIVE greedy slots are extractable (the router
        extracts after the final prefill chunk activates the slot)."""
        self._drain_async("boundary")
        self._refuse(self._looped, self.ut_steps > 1 and _LOOPED_HANDOFF)
        self._refuse(_STATEFUL, self.stateful and _HYBRID_HANDOFF)
        self._refuse(_LATENT, self.latent and _LATENT_HANDOFF)
        self._refuse(_MIXED, self.mixed and _MIXED_HANDOFF)
        if self.cp > 1:
            raise NotImplementedError(
                "KV handoff under context parallelism (cp>1) is not "
                "supported — the gather program reads a single-device "
                "pool; ship from/to cp=1 replicas")
        slots = np.nonzero(self.slot_req == rid)[0]
        if not len(slots) or rid in self.prefilling or rid in self.groups:
            raise ValueError(f"req {rid} holds no active greedy slot")
        slot = int(slots[0])
        if self.is_beam[slot] or not self.active[slot]:
            raise ValueError(f"req {rid} holds no active greedy slot")
        if rid in self._adapter_pins:
            raise NotImplementedError(
                "cannot extract a multi-LoRA sequence — its KV was "
                "written under the adapter, and the receiving replica "
                "holds no pin on it")
        t = self.mgr.tables[rid]
        if any(b is None for b in t):
            raise NotImplementedError(
                "cannot extract a window-recycled sequence (holes in the "
                "block table)")
        idx = np.zeros(self.max_blocks_per_seq, np.int32)
        idx[:len(t)] = t
        k, v = _GATHER_BLOCKS_JIT(self.cache.k_pools, self.cache.v_pools,
                                  jnp.asarray(idx))
        ks = vs = None
        if self.cache.k_scales:
            # int8 pool: the codes are meaningless without their scales —
            # gather the scale rows through the same program (distinct
            # compile entry; the trailing dims differ)
            ks, vs = _GATHER_BLOCKS_JIT(self.cache.k_scales,
                                        self.cache.v_scales,
                                        jnp.asarray(idx))
        payload = KVPayload(
            req=self.requests[rid], cur=int(self.cur[slot]),
            gen=int(self.gen[slot]), last_tok=int(self.last_tok[slot]),
            n_blocks=len(t), block_size=self.block_size, k=k, v=v,
            k_scale=ks, v_scale=vs)
        # wire contract: geometry + checksums recorded while the blocks
        # are known-good, so the router can reject a partial transfer
        payload.seal()
        # gather landed — now release host state (same order as cancel)
        REQUESTS.event(payload.req, "kv_extract", replica=self.trace_name,
                       blocks=len(t), cur=int(self.cur[slot]))
        self.mgr.free(rid)
        REQUESTS.event(payload.req, "kv_peak", replica=self.trace_name,
                       blocks=self.kv.take_peak(rid))
        self.kv.release(rid)
        self.active[slot] = False
        self.slot_req[slot] = -1
        self.draft_cur[slot] = 0
        self.slot_aidx[slot] = -1
        self._grammar.pop(slot, None)
        self.sched.release(rid)
        return payload

    def snapshot_session(self, rid: int):
        """Host-side durability capture (ISSUE 16): prompt + generated
        ids + sampler RNG + adapter/grammar refs for one in-flight
        request — everything a surviving replica needs to resume the
        session by replaying prefill. Token ids only, never KV blocks,
        so the capture is tick-cheap. Returns None for unknown/finished
        requests; the ``serving.snapshot`` chaos site fires pre-capture,
        so an injected fault skips this capture cleanly (the caller
        keeps its previous, staler snapshot)."""
        req = self.requests.get(rid)
        if req is None or req.done:
            return None
        # the snapshot's token list and rng must be mutually consistent:
        # land any in-flight async ticks before capturing either
        self._drain_async("boundary")
        if req.done:
            return None
        fault_point("serving.snapshot", engine=self, rid=rid)
        snap = SessionSnapshot(
            req_id=rid, prompt=req.prompt, tokens=tuple(req.tokens),
            session_id=req.session_id, tenant_id=req.tenant_id,
            adapter_id=req.adapter_id, grammar=req.grammar,
            rng=self.rng, gen=len(req.tokens),
            captured_t=self.sched.clock())
        _SNAPSHOTS.inc()
        return snap

    def install_sequence(self, payload: KVPayload) -> bool:
        """Adopt a sequence extracted from another replica: scatter its
        blocks into this pool, install the block-table row + length, and
        activate a slot mid-decode. Returns False (payload untouched, no
        state changed) when no slot or not enough blocks are free —
        the router retries later. Exception-atomic: host bookkeeping is
        undone if allocation fails; the donating scatter runs last."""
        self._drain_async("boundary")
        if self._draining:
            raise EngineDrainingError(
                "engine is draining — finishing in-flight requests, "
                "admitting nothing new")
        req = payload.req
        self._refuse(self._looped, self.ut_steps > 1 and _LOOPED_HANDOFF)
        self._refuse(_STATEFUL, self.stateful and _HYBRID_HANDOFF)
        self._refuse(_LATENT, self.latent and _LATENT_HANDOFF)
        self._refuse(_MIXED, self.mixed and _MIXED_HANDOFF)
        if self.cp > 1:
            raise NotImplementedError(
                "KV handoff under context parallelism (cp>1) is not "
                "supported — the install scatter writes a single-device "
                "pool; ship from/to cp=1 replicas")
        if req.adapter_id is not None:
            raise NotImplementedError(
                "multi-LoRA sequences do not ride the KV handoff (the "
                "payload's KV depends on adapter weights this engine "
                "has not pinned)")
        if payload.block_size != self.block_size:
            raise ValueError(f"block_size mismatch: payload "
                             f"{payload.block_size} != {self.block_size}")
        pool = self.cache.k_pools[0]
        if (payload.k.shape[0] != len(self.cache.k_pools)
                or payload.k.shape[2:] != pool.shape[1:]):
            raise ValueError("KV payload geometry does not match this "
                             "engine's pool (layers/heads/head_dim)")
        if (payload.k_scale is not None) != bool(self.cache.k_scales):
            raise ValueError("KV payload quantization does not match this "
                             "engine's pool — source and target replicas "
                             "must share kv_dtype")
        if payload.cur + self._remaining(req) > self.max_seq_len:
            raise ValueError("sequence + remaining tokens exceeds this "
                             "engine's max_seq_len")
        rid = req.req_id
        if rid in self.requests:
            raise ValueError(f"req_id {rid} already exists")
        free = np.nonzero(self.slot_req < 0)[0]
        wc = self.mgr.blocks_needed(payload.cur + self._remaining(req))
        if not len(free) or wc > self.mgr.free_blocks - self._reserved:
            return False
        slot = int(free[0])
        self.sched.adopt(req)
        self.kv.begin(rid, wc)
        try:
            t = self.mgr.allocate(rid, payload.cur)
        except MemoryError:
            self.kv.release(rid)
            self.sched.release(rid)
            return False
        self.kv.update(rid)
        # NOTE: the installed blocks are NOT committed to the prefix
        # cache — the normal admission path matches before allocating;
        # committing here could duplicate content already parked. Only
        # sharing is lost, never correctness.
        idx = np.full(self.max_blocks_per_seq, self.mgr.num_blocks,
                      np.int32)
        idx[:len(t)] = t
        row = np.full(self.max_blocks_per_seq, self.mgr.num_blocks,
                      np.int32)
        row[:len(t)] = t
        self.cache = _INSTALL_BLOCKS_JIT(
            self.cache, jnp.asarray(idx), payload.k, payload.v,
            payload.k_scale, payload.v_scale,
            jnp.int32(slot), jnp.asarray(row), jnp.int32(payload.cur))
        self.slot_req[slot] = rid
        self.active[slot] = True
        self.is_beam[slot] = False
        self.cur[slot] = payload.cur
        self.gen[slot] = payload.gen
        self.max_gen[slot] = payload.gen + self._remaining(req)
        self.table_len[slot] = len(t)
        self.last_tok[slot] = payload.last_tok
        self.temps[slot], self.top_ps[slot] = self._req_sampling(req)
        self._adm_counter += 1
        self.adm_order[slot] = self._adm_counter
        self.slot_aidx[slot] = -1
        # a grammar request resumes mid-stream: the mask state replays
        # the tokens it generated on the prefill replica
        self._bind_grammar(slot, req)
        # empty draft frontier: the decode replica's spec path re-feeds
        # the whole committed sequence through its own draft cache
        self.draft_cur[slot] = 0
        self.slot_k[slot] = self.spec_k
        self._acc_ema[slot] = 1.0
        REQUESTS.event(req, "kv_install", replica=self.trace_name,
                       blocks=payload.n_blocks, cur=payload.cur)
        return True

    # ------------------------------------------------- roofline anatomy
    @contextmanager
    def _tick_timer(self, name: str, span_name: str, **args):
        """Accumulate a named slice of the CURRENT tick's wall time
        (same clock as the tick total, so the breakdown reconciles) and
        mark it as the span ``span_name``: the span and the slice come
        from the same two clock reads."""
        t = time.monotonic_ns()
        sp = _span(span_name, **args).begin(t)
        try:
            yield sp
        finally:
            t1 = time.monotonic_ns()
            sp.end(t1)
            self._tick_phase[name] = (self._tick_phase.get(name, 0.0)
                                      + (t1 - t) * 1e-9)

    def _acc_phase(self, phase: str, tokens: int, passes: int, ctx: int):
        """Add one forward's roofline counts to a phase's cumulative
        [seconds, tokens, weight passes, KV-read positions] row (seconds
        arrive separately, from the tick timer in ``step``)."""
        row = self._phase_acc[phase]
        row[1] += tokens
        row[2] += passes
        row[3] += ctx

    def _ctx_blocks(self, mask) -> int:
        """Σ block-rounded attended context over masked slots: the fused
        decode kernel walks whole blocks of the table, so a single-query
        tick reads ceil(len/block)·block positions per slot."""
        lens = self.cur[mask] + 1
        bs = self.block_size
        return int((-(-lens // bs) * bs).sum())

    def _space_blocks(self, mask, ctx: int) -> dict:
        """``kv_blocks_full`` and ``kv_blocks_window`` of a decode tick of
        a model with two block spaces: the blocks a layer of each space
        walks for the masked slots (``ctx``: :meth:`_ctx_blocks` of them),
        a window layer from the block of ``len - window`` on. Nothing, and
        no work, for any other model."""
        if not self.mixed:
            return {}
        bs = self.block_size
        below = np.maximum(self.cur[mask] + 1 - self.window, 0) // bs
        return {"kv_blocks_full": ctx // bs,
                "kv_blocks_window": ctx // bs - int(below.sum())}

    @staticmethod
    def _ctx_causal(lens, offs) -> int:
        """Σ attended (query, position) pairs of a causal chunk batch:
        a chunk of L tokens at offset O attends L·O + L(L+1)/2 pairs."""
        ls = np.asarray(lens, np.int64)
        os_ = np.asarray(offs, np.int64)
        return int((ls * os_ + ls * (ls + 1) // 2).sum())

    def _push_roofline(self, sums):
        """Fold the cumulative phase accumulators ``sums`` through the
        roofline choke point (lifetime-average MFU/MBU per phase, same
        cumulative convention as the spec acceptance-rate gauge)."""
        if self._geom is None:
            return
        for phase, (sec, tok, passes, ctx) in sums.items():
            if sec <= 0.0 or tok <= 0:
                continue
            geom = self._draft_geom if phase == "spec_draft" else self._geom
            if geom is None:
                continue
            record_serving_throughput(
                phase, seconds=sec, tokens=tok, weight_passes=passes,
                kv_read_positions=ctx, geom=geom,
                peak_flops=self._peak_flops, peak_hbm_bps=self._peak_hbm)

    def _sweep_in_shadow(self, run_mask):
        """The tick's gauge sweep, between its decode dispatch and its
        fetch: nothing a sweep reads needs the tick's tokens, and here the
        host would only wait. Taken when the host knows the tick's end
        state already: nothing has left its place this tick
        (``_gauge_force``) and no running row is at its last token. A
        finish it cannot foresee (EOS, a stream callback's cancel) sets
        ``_gauge_force``, and the tick sweeps again at its end."""
        last = run_mask & ~self.is_beam & (self.gen + 1 >= self.max_gen)
        if self._gauge_force or last.any():
            return
        self._gauge_shadowed = True
        self._refresh_gauges(in_flight=int(run_mask.sum()))

    def _refresh_gauges(self, force=False, in_flight=0):
        """Point-in-time engine state → gauges (queue depth, active
        slots, KV-pool utilization): once a tick, in the shadow of its
        decode dispatch (:meth:`_sweep_in_shadow`, which gives
        ``in_flight``: the rows running, whose token ``cur`` does not
        count yet) or at its end. ``PT_GAUGE_EVERY_S`` (default 0 = every tick, so
        dumps and tests are unchanged) wall-clock-throttles the sweep for
        host-bound decode loops; drain/finish boundaries and run()-end
        pass ``force=True`` so final gauge values are always exact."""
        shadow = in_flight > 0
        if not force:
            try:
                every = float(os.environ.get("PT_GAUGE_EVERY_S", "0") or 0)
            except ValueError:
                every = 0.0
            if every > 0.0 and self._gauge_t is not None \
                    and time.monotonic() - self._gauge_t < every:
                return
        self._gauge_t = time.monotonic()
        self._gauge_sweeps += 1
        with _span("serving.gauges", shadow=shadow) as sweep:
            if self.mixed:
                # each space's blocks: held by a table, and free
                full, win = self.mgr, self.kv.window
                held = win.num_blocks - win.free_blocks
                _WINDOW_KV_IN_USE.set(held)
                sweep.set(full_held=full.num_blocks - full.free_blocks,
                          full_free=full.free_blocks, window_held=held,
                          window_free=win.free_blocks)
            if self.async_depth:
                _ASYNC_DEPTH.set(self.async_depth)
            _QUEUE_DEPTH.set(len(self.queue))
            _ACTIVE_SLOTS.set(int(self.active.sum()))
            used = self.mgr.num_blocks - self.mgr.free_blocks
            _KV_IN_USE.set(used)
            _KV_UTIL.set(used / self.mgr.num_blocks if self.mgr.num_blocks
                         else 0.0)
            self.kv.push_prefix_metrics()
            if self.stateful:
                per = self.stats["state_bytes_per_slot"]
                _STATE_BYTES.set(per * self.num_slots, kind="slots")
                _STATE_BYTES.set(per * self.mgr.snapshots_held(),
                                 kind="snapshots")
            # context parallelism (ISSUE 18): axis size + per-shard block
            # occupancy under the contiguous split. The gauge family stays
            # silent at cp=1 (no shard labels registered) so single-device
            # dumps are byte-identical to pre-cp runs.
            if self.cp > 1:
                _CP_AXIS.set(self.cp)
                ids = (b for t in self.mgr.tables.values() for b in t)
                for s, n in enumerate(shard_occupancy(
                        ids, self.mgr.num_blocks, self.cp)):
                    _CP_SHARD_BLOCKS.set(n, shard=str(s))
            led = self.kv.ledger
            if led.enabled:
                led.publish(bytes_per_block=self._kv_block_bytes(),
                            resident_tokens=(self._resident_tokens()
                                             + in_flight))
                # HBM gauges ship continuously, but the jax query is not
                # tick-cheap — refresh at most once a second (and on the
                # first sweep, so short runs still export them)
                now = time.monotonic()
                if self._dev_mem_t is None or now - self._dev_mem_t >= 1.0:
                    self._dev_mem_t = now
                    try:
                        device_memory_stats()
                    except Exception:
                        pass
            if not shadow:       # else at the tick's end: the emit adds to it
                GOODPUT.refresh_gauge()
            # degradation control loop: the gauge sweep doubles as the poll
            # cadence. A router-owned controller is polled by the router
            # only, so N replicas sharing it don't multiply the hysteresis
            # clock by N.
            if (self.degrade is not None
                    and self.degrade.owner in (None, self)):
                self.degrade.poll()
            # SLO burn-rate sweep rides the same cadence and the same
            # ownership protocol (a Router-claimed tracker is polled by the
            # router only)
            if self.slo is not None and self.slo.owner in (None, self):
                self.slo.poll()
            # in the shadow the tick's seconds are not known: push the
            # sums of whole ticks, which trail by the tick in flight
            self._push_roofline(self._roofline_sums if shadow
                                else self._phase_acc)

    def _kv_block_bytes(self) -> int:
        """HBM bytes one pool block holds across all layers (K and V,
        plus the scale pools of a quantized cache) — the actual stored
        dtypes, so ``serving_kv_bytes_per_token`` reports int8 pools at
        their true (halved) footprint."""
        if self._block_bytes is None:
            try:
                self._block_bytes = cache_block_bytes(self.cache)
            except Exception:
                self._block_bytes = 0
        return self._block_bytes

    def _resident_tokens(self) -> int:
        """Tokens whose KV currently sits in the pool (active slots'
        cache frontiers + consumed chunk-prefill spans)."""
        return (int(self.cur[self.active].sum())
                + sum(c for _, c in self.prefilling.values()))

    def step(self):
        """One engine tick — see :meth:`_step_impl`. Wrapped here so the
        tick lands in the trace timeline (the ``serving.step`` span, whose
        children are the tick's slices) and the tick-duration histogram
        even when a chaos rule or a dry pool raises out of the middle.
        The tick's anatomy (prefill/draft/verify/sample slices timed by
        :meth:`_tick_timer`, host = the remainder) goes to the breakdown
        histogram: all five phases observe every tick, so the five
        observations sum to the tick's total by construction."""
        t0 = time.monotonic()
        self._tick_phase = {}
        self._tick_no += 1
        self._gauge_shadowed = False
        with _span("serving.step", tick=self._tick_no):
            try:
                return self._step_impl()
            except BaseException:
                self._gauge_force = True     # whatever state it left
                raise
            finally:
                total = time.monotonic() - t0
                with _span("serving.bookkeeping"):
                    self._tick_bookkeeping(total)

    def _tick_bookkeeping(self, total: float):
        """What every tick owes the instruments once its work is done
        (the ``serving.bookkeeping`` span): histograms, the tenants'
        bill, the roofline accumulators, the gauges."""
        ph = self._tick_phase
        timed = sum(ph.values())
        for name in ("prefill", "draft", "verify", "sample"):
            _TICK_BREAKDOWN.observe(ph.get(name, 0.0), phase=name)
        _TICK_BREAKDOWN.observe(max(0.0, total - timed), phase="host")
        _TICK.observe(total)
        # usage metering (ISSUE 19): bill this tick's device time
        # and KV occupancy to the tenants holding state — the same
        # `total` the histogram just observed, so the ledger's
        # device-seconds reconcile with serving_tick_seconds
        # tick-for-tick
        if self.slo is not None:
            self.slo.charge_tick(self, total)
        acc = self._phase_acc
        acc["prefill"][0] += ph.get("prefill", 0.0)
        acc["spec_draft"][0] += ph.get("draft", 0.0)
        acc["spec_verify"][0] += ph.get("verify", 0.0)
        acc["decode"][0] += ph.get("sample", 0.0)
        # overlap-aware anatomy (ISSUE 20): host work done under an
        # in-flight device dispatch was folded into the "sample"
        # slice above (it is device-overlapped wall time, mirroring
        # PR 4's overlap-aware MFU) — surface it separately here so
        # "host" reports only EXPOSED host time while the five-phase
        # sum still equals the tick total
        if self.async_depth:
            _TICK_HIDDEN.observe(self._hidden_acc)
            self._hidden_acc = 0.0
        self._roofline_sums = {p: tuple(row) for p, row in acc.items()}
        force, self._gauge_force = self._gauge_force, False
        if force or not self._gauge_shadowed:
            self._refresh_gauges(force=force)
        else:
            GOODPUT.refresh_gauge()

    def _step_impl(self):
        """Exception-atomicity shim around :meth:`_step_inner` for the
        async pipeline (ISSUE 20): a fault raised mid-tick while
        dispatched-but-undrained ticks are in flight must not strand
        their tokens — drain the window (their emissions are exactly the
        tokens the synchronous engine produced in the preceding ticks,
        so the stream stays bit-identical), then re-raise. With an empty
        window this adds nothing to the sync path."""
        try:
            return self._step_inner()
        except BaseException:
            if self._async_win:
                self._drain_async("exception")
            raise

    # ------------------------------- async pipelined decode (ISSUE 20)
    def _spec_would_run(self) -> bool:
        """Mirror of the sync tick's speculative-decode gate: True when
        the next tick would draft-and-verify (host sampling every tick —
        the window must drain for it)."""
        return (self.draft_model is not None
                and (self.degrade is None or self.degrade.spec_enabled())
                and bool((self.active & ~self.is_beam
                          & (self.max_gen - self.gen >= 2)).any()))

    def _async_block_reason(self):
        """Why the NEXT tick cannot cruise in the async pipeline — None
        means pure decode (dispatch without fetching). Any non-None
        reason drains the window first, then the tick runs the ordinary
        synchronous path, so block-table mutations, host sampling, and
        the ledger stay tick-exact:

        mode     prefill-only replica / context-parallel engine
        admit    requests waiting for admission (scheduler runs host-side)
        prefill  chunked prefill in flight
        beam     beam groups need host select+fork every tick
        finish   no plain active slots (drain emits the tail, run() ends)
        grammar  constrained slots need the host automaton per token
        adapter  multi-LoRA rows compose per-slot corrections host-side
        window   sliding-window recycling mutates tables per tick
        spec     draft-and-verify samples on the host this tick
        growth   a slot would cross a block boundary within the window
        """
        if self.prefill_only or self.cp > 1:
            return "mode"
        if self.queue:
            return "admit"
        if self.prefilling:
            return "prefill"
        if self.groups or self.is_beam.any():
            return "beam"
        act = self.active & ~self.is_beam
        if not act.any():
            return "finish"
        if self._grammar:
            return "grammar"
        if self._adapter_pins:
            return "adapter"
        if self.window is not None:
            return "window"
        if self._spec_would_run():
            return "spec"
        # the host ``cur`` mirror lags by the window length: the tick
        # about to dispatch writes position cur + len(win), which must
        # already have a table entry (cruise never mutates tables)
        d = len(self._async_win)
        if (((self.cur[act] + d) // self.block_size)
                >= self.table_len[act]).any():
            return "growth"
        return None

    def _async_step(self):
        """One cruise tick of the depth-K pipeline: dispatch the next
        decode tick with the PREVIOUS tick's token array still on device
        (no fetch-reupload round trip), then — once the window exceeds
        ``async_depth`` — fetch and emit the OLDEST tick's tokens, hidden
        under the in-flight dispatch. EOS/max-gen stop is evaluated
        inside the tick jit via the device stop mask, so a slot that
        finished at tick N is masked out of tick N+1 even though the
        host has not seen its token yet."""
        act = self.active & ~self.is_beam
        # chaos parity with the sync tick: these sites fire BEFORE the
        # dispatch, so an injected exception aborts with the cache,
        # tables, and ledger untouched (the shim drains the window)
        if self._is_moe:
            fault_point("serving.moe_dispatch", engine=self,
                        slots=np.nonzero(act)[0])
        if self.exe.cache.k_scales:
            fault_point("serving.kv_quant", engine=self,
                        slots=np.nonzero(act)[0])
        dev = self._async_dev
        if dev is None:
            # window start: seed the device-resident loop state from the
            # host mirrors (exact — the window was just drained)
            dev = self._async_dev = {
                "tokens": jnp.asarray(self.last_tok),
                "stop": jnp.zeros(self.num_slots, bool),
                "gen": jnp.asarray(self.gen),
                "max_gen": jnp.asarray(self.max_gen),
            }
        eos = -1 if self.eos_token_id is None else int(self.eos_token_id)
        rng_before = self.exe.rng
        t0 = time.perf_counter()
        # the host's count: a row the device stopped and the host has not
        # seen yet still counts with its temperature (the program masks it)
        greedy = self._count_sampler(self.temps[act])
        with self._tick_timer("sample", "serving.decode",
                              slots=int(act.sum()), greedy=greedy,
                              **self.exe.span_args):
            nxt, ran, stop, gen = self.exe.decode_tick_async(
                dev["tokens"], act, dev["stop"], dev["gen"],
                dev["max_gen"], self.temps, self.top_ps, eos)
        self.stats["device_s"] += time.perf_counter() - t0
        dev["tokens"], dev["stop"], dev["gen"] = nxt, stop, gen
        self._async_rewound = False
        self._async_win.append({"nxt": nxt, "ran": ran,
                                "rng_before": rng_before,
                                "seq": self.exe.model_seq})
        self.stats["ticks"] += 1
        emitted = []
        if len(self._async_win) > self.async_depth:
            # steady state: drain exactly the oldest tick. The guard
            # keeps a stream-callback cancel() from recursively draining
            # the window out from under us (it detaches immediately; the
            # dead slot's in-flight rows bill GOODPUT async_overrun).
            self._async_draining = True
            try:
                emitted += self._drain_one()
            finally:
                self._async_draining = False
        return emitted

    def _drain_one(self):
        """Fetch + emit the oldest dispatched tick. The host mirrors
        (``cur``/``gen``/``last_tok``) advance HERE, at drain — so at
        every drain boundary they hold exactly the values the
        synchronous engine would. A fully-masked tick (every slot
        stopped on device before the host noticed) emits nothing and
        rewinds the executor rng to its pre-split state: the sync engine
        never ran that tick, so it never consumed that key."""
        e = self._async_win.pop(0)
        t0 = time.monotonic_ns()
        fetch = _span("serving.fetch", cat="device_wait",
                      seq=e["seq"]).begin(t0)
        nxt = np.asarray(e["nxt"])
        ran = np.asarray(e["ran"])
        t1 = time.monotonic_ns()
        fetch.end(t1)
        # the fetch blocks until that tick's device work completes:
        # device-overlapped wall time, billed to the "sample" slice
        self._tick_phase["sample"] = (self._tick_phase.get("sample", 0.0)
                                      + (t1 - t0) * 1e-9)
        self.stats["device_s"] += (t1 - t0) * 1e-9
        if not ran.any():
            if not self._async_rewound:
                self.exe.rng = e["rng_before"]
                self._async_rewound = True
            return []
        # roofline billed at drain, where cur is tick-exact: one weight
        # pass, each ran slot read its block-rounded context (same
        # accounting as the sync tick)
        self._acc_phase("decode", int(ran.sum()), 1, self._ctx_blocks(ran))
        live = ran & (self.slot_req >= 0)
        over = int(ran.sum() - live.sum())
        if over:
            # rows that ran on device for a slot the host has since torn
            # down (cancel from a stream callback mid-window): the sync
            # engine never computed these tokens — wasted work, never
            # emitted
            GOODPUT.waste("async_overrun", over)
        self.cur += live
        slots = np.nonzero(live)[0]
        t2 = time.monotonic_ns()
        emit = _span("serving.emit", tokens=len(slots)).begin(t2)
        emitted = []
        for slot in slots:
            emitted += self._emit(int(slot), int(nxt[slot]))
        t3 = time.monotonic_ns()
        emit.end(t3)
        host = (t3 - t2) * 1e-9
        self.stats["host_s"] += host
        if self._async_win:
            # successors are still in flight: this host work is hidden
            # under device dispatch. Fold it into the "sample" slice
            # (device-overlapped time) and surface it in the hidden-host
            # histogram; the final entry's emit is exposed host time and
            # falls through to the "host" remainder.
            self._hidden_acc += host
            self._tick_phase["sample"] = (
                self._tick_phase.get("sample", 0.0) + host)
        return emitted

    def _drain_async(self, why: str):
        """Drain the whole window (fetch + emit every dispatched tick),
        leaving the host mirrors tick-exact and the device loop state
        discarded (the next cruise re-seeds from the mirrors). No-op
        when the window is empty or a drain is already on the stack
        (stream-callback re-entrancy)."""
        if not self._async_win or self._async_draining:
            return []
        self._async_draining = True
        try:
            emitted = []
            while self._async_win:
                emitted += self._drain_one()
            self._async_dev = None
            _ASYNC_DRAINS.inc(why=why)
            self._gauge_force = True
            return emitted
        finally:
            self._async_draining = False

    def _step_inner(self):
        """One engine tick: advance in-flight beam groups (select + fork,
        or their final selection), admit waiting requests into free slots
        (their prefill runs now, interleaved with decode), then one decode
        tick for every active slot. Returns [(req_id, new_token), ...]
        (a finishing beam request emits its whole best hypothesis)."""
        # chaos hooks: serving.tick may raise/stall; serving.preempt rules
        # receive the engine and typically call engine._preempt() to
        # induce a preemption the pool never asked for
        fault_point("serving.tick", engine=self)
        fault_point("serving.preempt", engine=self)
        with _span("serving.expire"):
            self._expire()
        emitted = []
        if self.async_depth:
            why = self._async_block_reason()
            if why is None:
                return self._async_step()
            if self._async_win:
                # boundary: land every in-flight tick before the host
                # mutates tables/slots — the drained emissions belong to
                # this step's return
                emitted += self._drain_async(why)
        for rid in list(self.groups):
            emitted += self._beam_advance(rid, self.groups[rid])
        with _span("serving.admit") as sp:
            chunked = set(self.prefilling) if sp.recording else ()
            admits, beam_admits = self._admit()
            if sp.recording:     # long prompts go straight to prefilling
                rids = [r.req_id for _, r in (*admits, *beam_admits)]
                rids += [r for r in self.prefilling if r not in chunked]
                sp.set(admitted=len(rids), queued=len(self.queue), rids=rids)
        with self._tick_timer("prefill", "serving.prefill") as sp:
            self._prefill_sent, self._prefill_greedy = [0, 0], []
            if admits or beam_admits:
                emitted += self._prefill(admits, beam_admits)
            emitted += self._prefill_chunks()
            sp.set(live_rows=self._prefill_sent[0],
                   calls=self._prefill_sent[1])
            if self._prefill_greedy:
                sp.set(greedy=all(self._prefill_greedy))
        # what lies between the prefill calls and the decode dispatch: the
        # tick's inputs are built here, with nothing in flight
        with _span("serving.stage") as stage:
            preempted = self.stats["preemptions"]
            if self.prefill_only:
                # prefill-role replica: newly activated slots carry their
                # first token; the router extracts them — never decode here
                return emitted
            if not self.active.any():
                return emitted
            # speculative draft-and-verify for eligible slots; the plain
            # one-token tick then covers only what speculation did not handle
            # (beam slots, final-token slots, fallback after an injected
            # verify fault).
            spec_handled = np.zeros(self.num_slots, bool)
            if (self.draft_model is not None
                    and (self.degrade is None or self.degrade.spec_enabled())):
                elig = (self.active & ~self.is_beam
                        & (self.max_gen - self.gen >= 2))
                if elig.any():
                    spec_handled, spec_emitted = self._spec_tick(elig)
                    emitted += spec_emitted
            run_mask = self.active & ~spec_handled
            if not run_mask.any():
                # every active slot advanced speculatively: the whole point —
                # this tick paid ONE target forward for k+1 positions per slot
                return emitted
            t0 = time.perf_counter()
            if self._is_moe:
                # chaos: a dead expert shard fails the token all_to_all. Fires
                # BEFORE table growth and the donating tick jit, so an injected
                # exception aborts the tick with the cache, tables, and
                # table_len untouched — cancel/free reclaims every block and
                # assert_quiescent stays clean (exception-atomic).
                fault_point("serving.moe_dispatch", engine=self,
                            slots=np.nonzero(run_mask)[0])
            if self.exe.cache.k_scales:
                # chaos: quantize-on-write about to run inside the tick jit
                # (int8 pools only). Fires BEFORE table growth and the
                # donating tick, so an injected exception aborts with pools,
                # scale pools, tables, and the ledger untouched — no leaked
                # blocks, no stale scales (exception-atomic).
                fault_point("serving.kv_quant", engine=self,
                            slots=np.nonzero(run_mask)[0])
            if self.cp > 1:
                # chaos: the decode tick is about to run the cross-shard
                # partial gather (psum merge over cp). Fires BEFORE table
                # growth and the donating tick jit, so an injected exception
                # aborts the tick with the cache, tables, table_len, and the
                # ledger untouched — no leaked blocks, assert_quiescent and
                # reconcile stay clean (exception-atomic).
                fault_point("serving.cp_gather", engine=self,
                            slots=np.nonzero(run_mask)[0])
            rows, cols, vals, wvals = self._grow_tables(
                run_mask & ~self.is_beam)
            # growth may have preempted slots — recompute the mask after it
            run_mask = self.active & ~spec_handled
            # roofline: one weight pass over the batch; every running slot
            # reads its whole block-rounded context and writes one position
            n_run = int(run_mask.sum())
            greedy = self._count_sampler(self.temps[run_mask])
            ctx = self._ctx_blocks(run_mask)
            self._acc_phase("decode", n_run, 1, ctx)
            t1 = time.perf_counter()
            d_aidx = np.where(run_mask, self.slot_aidx, -1)
            d_bias = self._grammar_bias_rows(
                [(int(s), int(s)) for s in np.nonzero(run_mask)[0]],
                self.num_slots)
            if stage.recording:
                stage.set(grown=int((rows < self.num_slots).sum()),
                          preempted=self.stats["preemptions"] - preempted)
        # kv_blocks: the pool blocks the decode kernel walks this tick
        # (what is left of slots x table width)
        with self._tick_timer("sample", "serving.decode", slots=n_run,
                              greedy=greedy,
                              kv_blocks=ctx // self.block_size,
                              **self._space_blocks(run_mask, ctx),
                              **self.exe.span_args,
                              **self.exe.state_slots(n_run)) as stage:
            nxt, logp = self.exe.decode_tick(
                self.last_tok, run_mask, rows, cols, vals, self.temps,
                self.top_ps, bool(self.groups),
                lora=self._lora_arg(d_aidx, 1), bias=d_bias, wvals=wvals)
            was_active = run_mask.copy()
            # the program is queued: the copy of its tokens is asked for
            # now, and the host sweeps its gauges while the device works
            nxt.copy_to_host_async()
            self._sweep_in_shadow(run_mask)
            with _span("serving.fetch", cat="device_wait",
                       seq=self.exe.model_seq):
                nxt = np.asarray(nxt)         # the one per-tick host fetch
            if self.exe.routes and stage.recording:
                # the tick's two counts rode behind its tokens; the
                # prefill calls queued before it have run too
                stage.set(routed_pairs=int(nxt[-2]), experts_hit=int(nxt[-1]))
                self.exe.take_routed()
        t2 = time.perf_counter()
        if self.cp > 1:
            _CP_GATHER_S.observe(t2 - t1)
        for g in self.groups.values():        # device-resident, lazy gather
            g.logp = logp[np.asarray(g.slots)]
        self.cur += was_active                # vectorised mirrors
        plain = np.nonzero(was_active & ~self.is_beam)[0]
        with _span("serving.emit", tokens=len(plain)):
            for slot in plain:
                emitted += self._emit(slot, int(nxt[slot]))
        t3 = time.perf_counter()
        self.stats["host_s"] += (t1 - t0) + (t3 - t2)
        self.stats["device_s"] += t2 - t1
        self.stats["ticks"] += 1
        return emitted

    def run(self) -> dict:
        """Drain queue + slots; returns {req_id: generated token list}."""
        while self.has_work():
            self.step()
        # end-of-run gauges must be exact even under PT_GAUGE_EVERY_S
        self._refresh_gauges(force=True)
        return {rid: r.tokens for rid, r in self.requests.items()}
