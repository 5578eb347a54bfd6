"""Multi-replica router (ISSUE 7): greedy identity single vs routed vs
disaggregated prefill/decode (chunked prefill and spec decode included),
least-outstanding-requests dispatch, session affinity, health-gated
dispatch, drain-aware rebalancing (the requeue-before-drain deadlock
fix), the three router chaos sites, and the PT_ROUTER_DISAGG kill
switch. Every chaos path must leave the fleet quiescent — no block
leaks on any replica, dead ones included."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.health import (HEALTH, HealthEvaluator,
                                             gauge_imbalance)
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import (EngineDrainingError, LLMEngine, Replica,
                                Request, Router)
from paddle_tpu.utils.faults import FAULTS, InjectedFault

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module", autouse=True)
def _preserve_global_rng():
    """Later test modules build models off the global key stream without
    reseeding; leave that stream exactly where this module found it."""
    from paddle_tpu.core import random as _prng
    saved = None if _prng._global is None else _prng._global.key
    yield
    if saved is None:
        _prng._global = None
    else:
        _prng.seed(0)
        _prng._global.key = saved


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def draft():
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _mk(model, **kw):
    args = dict(num_slots=4, block_size=4, max_prompt_len=16,
                max_seq_len=48)
    args.update(kw)
    return LLMEngine(model, **args)


def _prompts(n, rs, lo=3, hi=14):
    return [rs.randint(0, 64, (int(l),)) for l in rs.randint(lo, hi, size=n)]


def _reference(model, prompts, max_new=10, **ekw):
    eng = _mk(model, **ekw)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new))
    return {rid: list(map(int, t)) for rid, t in eng.run().items()}


def _route(router, prompts, max_new=10, **rkw):
    for p in prompts:
        router.add_request(Request(p, max_new_tokens=max_new, **rkw))
    out = router.run()
    return {rid: list(map(int, t)) for rid, t in out.items()}


def _requeues_by_label():
    """router_requeues_total broken down as {(replica, why): count}."""
    from paddle_tpu.observability import METRICS
    inst = METRICS.get("router_requeues_total")
    if inst is None:
        return {}
    return {key: cell[0] for key, cell in inst._series.items()}


# --------------------------------------------------- greedy identity

def test_routed_two_replicas_matches_single_engine(model):
    """The router is transparent: 2-replica LOR output == one engine."""
    rs = np.random.RandomState(0)
    prompts = _prompts(8, rs)
    ref = _reference(model, prompts)
    r = Router([_mk(model), _mk(model)])
    out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["dispatched"] == 8 and r.stats["requeues"] == 0


def test_disaggregated_matches_single_engine(model):
    """1 prefill + 1 decode replica: every sequence crosses the KV
    transfer seam, and output is still token-for-token identical —
    including a prompt long enough for chunked prefill on the
    prefill-role replica (19 tokens > max_prompt_len=8 → 3 chunks)."""
    rs = np.random.RandomState(1)
    prompts = _prompts(5, rs) + [rs.randint(0, 64, (19,))]
    ref = _reference(model, prompts, max_prompt_len=8)
    r = Router([Replica(_mk(model, max_prompt_len=8), role="prefill"),
                Replica(_mk(model, max_prompt_len=8), role="decode")])
    assert r.disagg
    out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["transfers"] == 6          # every request crossed over
    assert not r.replicas[0].engine.has_work()


def test_disagg_spec_decode_on_decode_replica(model, draft):
    """Speculative decoding runs on the DECODE replica over installed
    (transferred) KV state: greedy output still equals the plain
    single-engine run."""
    rs = np.random.RandomState(2)
    prompts = _prompts(4, rs)
    ref = _reference(model, prompts, max_new=8)
    r = Router([
        Replica(_mk(model), role="prefill"),
        Replica(_mk(model, draft_model=draft, spec_k=2), role="decode"),
    ])
    out = _route(r, prompts, max_new=8)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["transfers"] == 4


def test_disagg_kill_switch(model, monkeypatch):
    """PT_ROUTER_DISAGG=0 collapses a disaggregated topology to plain
    replication: no transfers, roles coerced to 'both', output intact."""
    monkeypatch.setenv("PT_ROUTER_DISAGG", "0")
    rs = np.random.RandomState(3)
    prompts = _prompts(6, rs)
    ref = _reference(model, prompts)
    r = Router([Replica(_mk(model), role="prefill"),
                Replica(_mk(model), role="decode")])
    assert not r.disagg
    assert all(rep.role == "both" for rep in r.replicas)
    out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["transfers"] == 0


@pytest.mark.slow
def test_parallel_run_matches_sequential(model):
    """run(parallel=True) — one driver thread per replica — produces
    the same greedy tokens as orchestrated sequential stepping."""
    rs = np.random.RandomState(4)
    prompts = _prompts(8, rs)
    ref = _reference(model, prompts)
    r = Router([_mk(model), _mk(model)])
    for p in prompts:
        r.add_request(Request(p, max_new_tokens=10))
    out = {rid: list(map(int, t))
           for rid, t in r.run(parallel=True).items()}
    assert out == ref
    r.assert_quiescent()


# ------------------------------------------------- dispatch policy

def test_lor_prefers_least_loaded_replica(model):
    """Skewed lengths: once the short request finishes, its replica has
    the fewest outstanding requests and MUST win the next dispatch."""
    rs = np.random.RandomState(5)
    r = Router([_mk(model), _mk(model)])
    long_rid = r.add_request(Request(rs.randint(0, 64, (5,)),
                                     max_new_tokens=24))
    short_rid = r.add_request(Request(rs.randint(0, 64, (5,)),
                                      max_new_tokens=2))
    assert r._where[long_rid] == 0 and r._where[short_rid] == 1
    while not r.requests[short_rid].done:
        r.step()
    nxt = r.add_request(Request(rs.randint(0, 64, (5,)), max_new_tokens=2))
    assert r._where[nxt] == 1          # r1 idle, r0 still decoding
    r.run()
    r.assert_quiescent()


def test_session_affinity_sticks_to_one_replica(model):
    """Requests sharing a session_id land on one replica (their prefix
    blocks live there); distinct sessions still spread by LOR."""
    rs = np.random.RandomState(6)
    r = Router([_mk(model), _mk(model)])
    alice = [r.add_request(Request(rs.randint(0, 64, (6,)),
                                   max_new_tokens=6, session_id="alice"))
             for _ in range(3)]
    bob = [r.add_request(Request(rs.randint(0, 64, (6,)),
                                 max_new_tokens=6, session_id="bob"))
           for _ in range(3)]
    assert len({r._where[rid] for rid in alice}) == 1
    assert len({r._where[rid] for rid in bob}) == 1
    assert r._where[alice[0]] != r._where[bob[0]]
    r.run()
    r.assert_quiescent()


def test_crit_replica_receives_nothing(model):
    """Health gating: a replica whose evaluator verdicts CRIT is
    excluded from dispatch entirely."""
    rs = np.random.RandomState(7)
    bad = Replica(_mk(model))
    bad.health.rule("always_on_fire", lambda: 99.0, warn=1.0, crit=2.0)
    r = Router([bad, Replica(_mk(model))])
    prompts = _prompts(5, rs)
    ref = _reference(model, prompts)
    out = _route(r, prompts)
    assert out == ref
    assert bad.engine.stats["ticks"] == 0    # never even stepped
    r.assert_quiescent()


def test_imbalance_health_rule_installed_and_fires(model):
    """Router construction installs the stock imbalance rule on the
    global evaluator; the gauge_imbalance getter flags a skewed fleet."""
    Router([_mk(model), _mk(model)])
    assert any(rule.name == "router_replica_imbalance"
               for rule in HEALTH.rules)
    reg = MetricsRegistry()
    g = reg.gauge("router_replica_outstanding", "t", labelnames=("replica",))
    get = gauge_imbalance("router_replica_outstanding", registry=reg)
    g.set(10.0, replica="a")
    assert np.isnan(get())            # one series: nothing to compare
    g.set(0.0, replica="b")
    assert get() == pytest.approx(2.0)   # (10-0)/max(mean=5, 1)
    g.set(10.0, replica="b")
    assert get() == pytest.approx(0.0)


# ----------------------------------------------------- drain/rebalance

def test_drain_replica_rebalances_without_deadlock(model):
    """Satellite (f): draining a replica while the router holds queued
    work for it must requeue-then-drain, not deadlock. Engines are
    sized so the fleet backs up into the router queue first."""
    rs = np.random.RandomState(8)
    prompts = _prompts(10, rs)
    ref = _reference(model, prompts, max_new=6)
    r = Router([_mk(model, num_slots=2, max_queue_len=2),
                _mk(model, num_slots=2, max_queue_len=2)])
    for p in prompts:
        r.add_request(Request(p, max_new_tokens=6))
    assert len(r._queue) > 0           # fleet full: router is holding work
    r.drain_replica("r0")              # must return, not spin
    assert r.replicas[0].draining
    out = {rid: list(map(int, t)) for rid, t in r.run().items()}
    assert out == ref
    r.assert_quiescent()
    # nothing new landed on r0 after the drain call finished it
    assert all(i != 0 for i in r._where.values())
    assert r.stats["requeues"] >= 1    # engine-queued work was rebalanced
    # every requeue carries the drained replica + the drain cause
    by = _requeues_by_label()
    assert by and all(k == ("r0", "drain") for k in by)
    assert sum(by.values()) == r.stats["requeues"]


def test_drain_prefill_replica_flushes_handoffs(model):
    """Draining a prefill-role replica mid-CHUNKED-prefill drives the
    extract/install loop to completion (a prefill-only engine can't
    finish slots by itself — plain engine.drain() would spin)."""
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 64, (19,))] + _prompts(3, rs, hi=8)
    ref = _reference(model, prompts, max_new=6, max_prompt_len=8)
    r = Router([Replica(_mk(model, max_prompt_len=8), role="prefill"),
                Replica(_mk(model, max_prompt_len=8), role="decode")])
    for p in prompts:
        r.add_request(Request(p, max_new_tokens=6))
    r.step()                    # 19-token prompt is now mid-chunk on r0
    r.drain_replica("r0")
    assert not r.replicas[0].engine.has_work()
    out = {rid: list(map(int, t)) for rid, t in r.run().items()}
    assert out == ref
    r.assert_quiescent()


# ------------------------------------------------------- chaos sites

def test_chaos_dispatch_requeues_and_recovers(model):
    """router.dispatch fault fires BEFORE the engine sees the request:
    nothing leaks, the request stays with the router and goes out on a
    later attempt; output identical."""
    rs = np.random.RandomState(10)
    prompts = _prompts(6, rs)
    ref = _reference(model, prompts)
    r = Router([_mk(model), _mk(model)])
    with FAULTS.scope("router.dispatch", exc=InjectedFault, on={0, 2}):
        out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["requeues"] == 2
    assert r.stats["dispatched"] == 6
    assert sum(n for (rep, why), n in _requeues_by_label().items()
               if why == "dispatch_fault") == 2


def test_chaos_kv_transfer_requeues_no_leak(model):
    """router.kv_transfer fault during the prefill→decode handoff:
    exception-atomic — the sequence is pulled back, requeued, and
    re-prefilled elsewhere; no blocks leak on either replica and greedy
    output is unchanged."""
    rs = np.random.RandomState(11)
    prompts = _prompts(5, rs)
    ref = _reference(model, prompts)
    r = Router([Replica(_mk(model), role="prefill"),
                Replica(_mk(model), role="decode")])
    with FAULTS.scope("router.kv_transfer", exc=InjectedFault, on={1, 3}):
        out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["requeues"] == 2
    # the faults fired on the prefill replica's extraction path
    assert _requeues_by_label() == {("r0", "kv_transfer"): 2}


def test_chaos_replica_death_requeues_exactly_once(model):
    """Kill r0 mid-decode: its live requests are pulled back and
    re-dispatched to r1 EXACTLY once each; finished work survives; the
    dead replica's pool shows no leaks; greedy output is unchanged."""
    rs = np.random.RandomState(12)
    prompts = _prompts(6, rs)
    ref = _reference(model, prompts)
    r = Router([_mk(model), _mk(model)])
    seen = {"r0": 0}

    def kill_r0(ctx):
        if ctx["replica"] == "r0":
            seen["r0"] += 1
            if seen["r0"] == 3:       # a few steps in: requests mid-decode
                raise InjectedFault("induced r0 death")

    with FAULTS.scope("router.replica_death", action=kill_r0):
        out = _route(r, prompts)
    assert out == ref
    r.assert_quiescent()
    assert r.stats["deaths"] == 1
    assert not r.replicas[0].alive
    assert r.stats["requeues"] == len(r._requeued) >= 1
    by = _requeues_by_label()
    assert by and all(k == ("r0", "replica_death") for k in by)
    assert sum(by.values()) == r.stats["requeues"]


def test_replica_death_twice_marks_request_failed(model):
    """A request whose SECOND replica also dies is not requeued again —
    it finishes with finish_reason='replica_death' (exactly-once
    requeue); survivors complete on the remaining replica and the whole
    fleet stays quiescent."""
    rs = np.random.RandomState(13)
    prompts = _prompts(6, rs)
    r = Router([_mk(model), _mk(model), _mk(model)])
    seen = {"r0": 0, "r1": 0}

    def kill_two(ctx):
        name = ctx["replica"]
        if name in seen:
            seen[name] += 1
            if (name, seen[name]) in (("r0", 2), ("r1", 6)):
                raise InjectedFault(f"induced {name} death")

    ref = _reference(model, prompts)
    with FAULTS.scope("router.replica_death", action=kill_two):
        for p in prompts:
            r.add_request(Request(p, max_new_tokens=10))
        out = r.run()
    assert r.stats["deaths"] == 2
    for rid, req in r.requests.items():
        assert req.done
        if req.finish_reason == "replica_death":
            continue                   # gave up after the second death
        assert list(map(int, out[rid])) == ref[rid]
    # exactly-once: every requeue is a distinct request
    assert r.stats["requeues"] == len(r._requeued)
    r.assert_quiescent()


def test_all_replicas_down_rejects_new_requests(model):
    rs = np.random.RandomState(14)
    r = Router([_mk(model)])
    r.replicas[0].alive = False
    with pytest.raises(EngineDrainingError):
        r.add_request(Request(rs.randint(0, 64, (5,)), max_new_tokens=4))


# ------------------------------------------------------ import surface

def test_serving_import_surface_unchanged():
    """The package split must not break a single pre-existing import."""
    import paddle_tpu.serving as S
    for name in ("LLMEngine", "Request", "QueueFullError",
                 "EngineDrainingError", "_BeamGroup", "_SAMPLE_ROWS_JIT",
                 "_MOE_DROPPED", "KVCache", "_sample_rows", "PagedKVCache",
                 "RadixPrefixBlockManager", "_beam_finalize",
                 "_BEAM_GROUP_UPDATE_JIT", "_BEAM_SELECT_JIT",
                 "_PREFILL_CHUNK_JIT", "_PREFILL_JIT", "_REWIND_LENS_JIT",
                 "_TICK_JIT", "_VERIFY_CHUNK_JIT", "greedy_accept_length",
                 "is_moe_model", "stochastic_accept_row", "_FWD_ROWS_JIT",
                 "METRICS", "_span", "FLIGHT", "fault_point",
                 "Router", "Replica", "Scheduler", "KVManager",
                 "ModelExecutor", "KVTransfer", "DeviceKVTransfer",
                 "KVPayload"):
        assert hasattr(S, name), f"paddle_tpu.serving lost {name}"
