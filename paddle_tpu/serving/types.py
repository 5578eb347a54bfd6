"""Request/response types and intake errors shared by the serving layers.

Split out of the monolithic ``serving.py`` (ISSUE 7) so the scheduler,
KV-manager, executor, engine, and router can all import them without
cycles. Everything here is host-side dataclass state — nothing traces
into a jitted program.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


class QueueFullError(RuntimeError):
    """Admission queue at ``max_queue_len`` — backpressure: the caller
    should shed load or retry later, NOT buffer unboundedly here."""


class EngineDrainingError(RuntimeError):
    """``drain()`` was called — the engine finishes in-flight work but
    admits nothing new."""


class OverloadError(QueueFullError):
    """The degradation ladder is at L4: new sessions are rejected with
    explicit backpressure until the cluster recovers. A subclass of
    :class:`QueueFullError` so existing shed/retry handlers compose —
    the correct client reaction (back off, retry later) is the same."""


@dataclass
class Request:
    """One generation request. ``stream`` (optional) is called as
    ``stream(request, token)`` the tick each new token is sampled.
    ``num_beams > 1``: beam search — the request occupies num_beams cache
    slots, selection mirrors ``decoding.beam_search`` exactly, and the
    BEST hypothesis lands in ``tokens`` when the request finishes (no
    streaming; tail past a hypothesis' first EOS is EOS-filled)."""
    prompt: object                       # 1-D int tokens
    max_new_tokens: int = 32
    req_id: int = None
    stream: object = None
    num_beams: int = 1
    length_penalty: float = 1.0
    # per-request sampling overrides (None = the engine's defaults):
    temperature: float = None
    top_p: float = None
    # robustness knobs (None = unbounded):
    #   deadline_s    total wall-clock budget from submission — expired
    #                 requests finish with finish_reason="timeout"
    #                 (whatever tokens were generated stay available)
    #   max_queue_s   max time WAITING for admission; a request that
    #                 can't enter a slot in time also times out
    deadline_s: float = None
    max_queue_s: float = None
    # router affinity (ISSUE 7): requests sharing a session_id stick to
    # one replica, so a session's prefix-cache blocks stay local
    session_id: object = None
    # multi-tenancy (ISSUE 14):
    #   adapter_id   LoRA adapter this request decodes under (must be
    #                registered with the engine's AdapterStore); None =
    #                the base model. Also part of the prefix-cache key —
    #                KV blocks never cross adapter identities.
    #   tenant_id    fair-scheduling identity: queued tenants share
    #                admission capacity by token-budget-weighted deficit
    #                (None = legacy FCFS ordering among the unlabelled)
    #   grammar      constrained decoding: a TokenMaskAutomaton (or a
    #                (regex, vocab) construction handled by the caller) —
    #                every sampled/accepted token satisfies its mask
    adapter_id: object = None
    tenant_id: object = None
    grammar: object = None
    # filled by the engine:
    tokens: list = field(default_factory=list)   # generated tokens
    done: bool = False
    finish_reason: str = None
    _submit_t: float = None              # engine clock at add_request
    _first_tok_t: float = None           # engine clock at first token (TTFT)
    _last_tok_t: float = None            # engine clock at newest token
    beam_score: float = None
    # set on preemption: prompt + tokens generated so far — the resume
    # prefill recomputes the whole sequence (prefix-cache hits make the
    # recompute cheap when its old blocks are still parked)
    _resume: object = None
    # scheduler-side prefix-match memo: (cache_epoch, prompt_len, match).
    # A queued request is re-probed only when the manager's epoch moved
    # (eviction/commit) or its effective prompt changed (resume)
    _match_memo: tuple = None
    # token span adopted from the radix prefix cache at admission (ISSUE
    # 11): the spec-decode draft seed uses it to skip re-embedding the
    # adopted prefix when the draft cache still holds those tokens
    _adopted: int = 0
    # a model with recurrent layers: (token depth, snapshot entry) of the
    # state snapshot this request's prefill is to take on its way
    _snapshot_plan: tuple = None
    # request tracker (ISSUE 9): trace_id is minted at first submit while
    # tracking is enabled (None = untracked, every tracker call no-ops);
    # trace_summary is the finished timeline summary, same dict /requests
    # serves
    trace_id: object = None
    trace_summary: object = None
    # set by the Router once ITS admission gate (queue depth + ladder
    # L4) has passed — replica engines then skip their own session gate,
    # so accepted work is never re-rejected mid-dispatch or on requeue
    _preadmitted: bool = False

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)


@dataclass
class _BeamGroup:
    """Engine-side state of one in-flight beam request (K cache slots +
    the device-resident selection state shared with paged_beam_search)."""
    req: Request
    slots: list
    s: int                                # prompt length
    i: int = 0                            # selects done
    sid: dict = field(default_factory=dict)   # beam j -> BlockManager key
    running_lp: object = None
    seqs: object = None
    fin_seqs: object = None
    fin_scores: object = None
    logp: object = None                   # [K, vocab] device, pre-select
