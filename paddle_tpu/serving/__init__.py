"""LLM serving: continuous-batching engine + multi-replica router.

The monolithic ``paddle_tpu/serving.py`` is now a package (ISSUE 7):

  * :mod:`.engine`    — ``LLMEngine``, the per-replica orchestrator
  * :mod:`.scheduler` — admission/deadlines/preemption/backpressure
  * :mod:`.kv`        — block tables, prefix cache, reservation ledger
  * :mod:`.executor`  — the jitted prefill/decode/verify programs
  * :mod:`.router`    — LOR dispatch over N replicas, session affinity,
                        health gating, disaggregated prefill/decode
  * :mod:`.transfer`  — the KV handoff seam between replicas
  * :mod:`.adapters`  — multi-tenant LoRA adapter store (device LRU)
  * :mod:`.grammar`   — token-mask automata for constrained decoding

Everything the old module exported is re-exported here, so
``from paddle_tpu.serving import LLMEngine, Request`` and every other
pre-split import keeps working unchanged.
"""
from paddle_tpu.models.decoding import KVCache, _sample_rows  # noqa: F401
from paddle_tpu.models.paged import (  # noqa: F401
    PagedKVCache, PrefixMatch, RadixPrefixBlockManager, _beam_finalize,
    _BEAM_GROUP_UPDATE_JIT, _BEAM_SELECT_JIT, _PREFILL_CHUNK_JIT,
    _PREFILL_JIT, _REWIND_LENS_JIT, _TICK_JIT, _VERIFY_CHUNK_JIT,
    greedy_accept_length, is_moe_model, stochastic_accept_row)
from paddle_tpu.models.speculative import _FWD_ROWS_JIT  # noqa: F401
from paddle_tpu.observability import METRICS, span as _span  # noqa: F401
from paddle_tpu.observability.flight import FLIGHT  # noqa: F401
from paddle_tpu.utils.faults import fault_point  # noqa: F401

from paddle_tpu.serving.adapters import AdapterStore  # noqa: F401
from paddle_tpu.serving.degrade import (  # noqa: F401
    DegradationController, SessionSnapshot, default_signals)
from paddle_tpu.serving.engine import LLMEngine  # noqa: F401
from paddle_tpu.serving.grammar import (  # noqa: F401
    TokenMaskAutomaton, json_schema_regex)
from paddle_tpu.serving.executor import (  # noqa: F401
    ModelExecutor, _SAMPLE_ROWS_JIT)
from paddle_tpu.serving.kv import KVManager  # noqa: F401
from paddle_tpu.serving.router import Replica, Router  # noqa: F401
from paddle_tpu.serving.scheduler import Scheduler  # noqa: F401
from paddle_tpu.serving.telemetry import (  # noqa: F401
    _ACTIVE_SLOTS, _ADMITTED, _CANCELLED, _DRAIN, _FINISHED, _KV_IN_USE,
    _KV_UTIL, _MOE_DROPPED, _PREEMPTED, _PREFIX_EVICTIONS, _PREFIX_HIT_RATE,
    _PREFIX_HITS, _QUEUE_DEPTH, _QUEUE_WAIT, _R_DEATHS, _R_DISPATCH,
    _R_HEALTH, _R_OUTSTANDING, _R_REQUEUES, _R_TRANSFER_BLOCKS,
    _R_TRANSFERS, _REJECTED, _SPEC_ACCEPTED, _SPEC_FALLBACKS,
    _SPEC_PROPOSED, _SPEC_RATE, _SPEC_TOKENS, _TICK, _TIMEOUTS, _TOK_LAT,
    _TOKENS, _TTFT)
from paddle_tpu.serving.transfer import (  # noqa: F401
    DeviceKVTransfer, KVPayload, KVTransfer, KVTransferError,
    TransportPolicy, validate_payload)
from paddle_tpu.serving.types import (  # noqa: F401
    EngineDrainingError, OverloadError, QueueFullError, Request,
    _BeamGroup)

__all__ = [
    "LLMEngine", "Request", "QueueFullError", "EngineDrainingError",
    "OverloadError",
    "Router", "Replica", "Scheduler", "KVManager", "ModelExecutor",
    "KVTransfer", "DeviceKVTransfer", "KVPayload", "KVTransferError",
    "TransportPolicy", "validate_payload",
    "DegradationController", "SessionSnapshot", "default_signals",
    "AdapterStore", "TokenMaskAutomaton", "json_schema_regex",
]
