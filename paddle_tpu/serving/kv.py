"""KVManager: block tables, prefix cache, and the reservation ledger.

The middle layer of the decomposed engine (ISSUE 7). It owns the block
manager — :class:`~paddle_tpu.models.paged.RadixPrefixBlockManager`
(token-span radix trie, copy-on-write partial-block reuse) — plus the
RESERVATION LEDGER the admission discipline runs on: ``need[rid]`` is a
request's worst-case block count, ``resv[rid]`` the part not yet
materialised as live table entries, and ``reserved`` their sum — the
blocks the free list must keep clear of other requests. The scheduler
decides WHO gets blocks; this layer tracks what was promised.
"""
from __future__ import annotations

from paddle_tpu.models.paged import (RadixPrefixBlockManager,
                                     TwoSpaceBlockManager)
from paddle_tpu.observability.flight import FLIGHT
from paddle_tpu.serving.telemetry import (_PREFIX_EVICTIONS,
                                          _PREFIX_HIT_RATE, _PREFIX_HITS,
                                          _PREFIX_PARTIAL_HITS,
                                          _PREFIX_TOKEN_HIT_RATE,
                                          _PREFIX_TOKEN_HITS,
                                          _STATE_SNAPSHOTS)


def cache_block_bytes(cache) -> int:
    """HBM bytes ONE pool block holds across all layers — K and V codes
    at their ACTUAL stored dtype, plus the parallel scale pools of a
    quantized cache (ISSUE 17). The memledger's bytes_per_token gauges
    divide by this, so an int8 pool reports its true (roughly halved)
    footprint instead of a bf16 assumption."""
    import numpy as np
    pools = (*cache.k_pools, *cache.v_pools,
             *getattr(cache, "k_scales", ()),
             *getattr(cache, "v_scales", ()))
    # a looped model's pool holds a row of every pass for each block
    return getattr(cache, "passes", 1) * sum(
        int(np.prod(p.shape[1:])) * p.dtype.itemsize for p in pools)


class KVManager:
    """Block allocation + worst-case reservation accounting."""

    def __init__(self, num_blocks: int, block_size: int,
                 window_blocks: int = 0):
        # refcounted + prefix-cached: beam groups share prompt blocks
        # copy-on-write; requests with equal prompt prefixes share the
        # prefix blocks outright (prefill only runs on the uncached
        # suffix); with no sharing it behaves exactly like BlockManager.
        # The radix trie matches token spans and reuses partial blocks
        # copy-on-write.
        # ``window_blocks`` > 0: a model with window layers beside full
        # ones. ``mgr`` is then the FULL space's manager, everything below
        # (the ledger, the reservations) is that space's as ever, and the
        # window space's manager is ``window`` (None for any other model),
        # with a ledger of promises of its own (``promise_window``)
        self.mgr = (TwoSpaceBlockManager(num_blocks, block_size,
                                         window_blocks)
                    if window_blocks
                    else RadixPrefixBlockManager(num_blocks, block_size))
        self.window = getattr(self.mgr, "window", None)
        self.window_promised = 0            # window blocks promised in all
        self.window_need: dict[int, int] = {}   # req_id -> its promise
        self.stateful = False        # until ``keep_state``
        # the block manager owns the per-pool memory ledger (its own
        # mutation choke points notify it); this layer mirrors the
        # reservation count into it and exposes the forensic wrappers
        self.ledger = self.mgr.ledger
        self.reserved = 0            # blocks promised to in-flight requests
        self.resv: dict[int, int] = {}    # req_id -> outstanding reserve
        self.need: dict[int, int] = {}    # req_id -> worst-case blocks
        self._prefix_pushed = dict(self.mgr.cache_stats)

    # --------------------------------------------------- pool passthroughs
    @property
    def num_blocks(self):
        return self.mgr.num_blocks

    @property
    def block_size(self):
        return self.mgr.block_size

    @property
    def free_blocks(self):
        return self.mgr.free_blocks

    @property
    def tables(self):
        return self.mgr.tables

    def blocks_needed(self, n_tokens: int) -> int:
        return self.mgr.blocks_needed(n_tokens)

    def allocate(self, rid: int, n_tokens: int):
        return self.mgr.allocate(rid, n_tokens)

    def free(self, rid: int):
        self.mgr.free(rid)

    def keep_state(self, snapshots: int):
        """The model carries recurrent state: two kinds of cache in one
        manager, a snapshot pool of ``snapshots`` entries (it may be 0)
        beside the blocks. A K/V match is from now on worth only as far
        as a snapshot of the state exists (``match``)."""
        self.stateful = True
        self.mgr.enable_snapshots(snapshots)

    def match(self, tokens, adapter=None):
        """The prefix an admission may adopt: the radix trie's K/V match,
        cut down to its deepest state snapshot where the model carries
        recurrent state (``RadixPrefixBlockManager.state_hit``)."""
        m = self.mgr.match_prefix(tokens, adapter=adapter)
        return self.mgr.state_hit(m) if self.stateful else m

    # ------------------------------------------------------------- ledger
    def live_blocks(self, rid: int) -> int:
        """Blocks currently held (window recycling leaves None holes)."""
        return sum(b is not None for b in self.mgr.tables.get(rid, []))

    def begin(self, rid: int, need: int):
        """Open a ledger entry: worst case recorded, nothing held yet."""
        self.need[rid] = need
        self.resv[rid] = 0

    def hold(self, rid: int, n: int):
        """Set the outstanding reserve to ``n`` blocks (chunk-prefill and
        beam admissions hold their whole worst case up front)."""
        self.reserved += n - self.resv.get(rid, 0)
        self.resv[rid] = n
        self.ledger.set_reserved(self.reserved)

    def update(self, rid: int, live: int = None):
        """Outstanding reserve = worst case minus blocks currently held
        (recycling under a sliding window RETURNS headroom). Beam groups
        pass their deduplicated ``live`` count (shared prompt blocks
        appear in several beams' tables)."""
        if live is None:
            live = self.live_blocks(rid)
        new = max(0, self.need[rid] - live)
        self.reserved += new - self.resv[rid]
        self.resv[rid] = new
        self.ledger.set_reserved(self.reserved)

    def release(self, rid: int):
        """Close the ledger entry, returning its reserve to the pool."""
        self.reserved -= self.resv.pop(rid, 0)
        self.need.pop(rid, None)
        self.ledger.set_reserved(self.reserved)
        if self.window_need:
            self.window_promised -= self.window_need.pop(rid, 0)

    # ---- the window space's own ledger (a model with two block spaces):
    # a request is promised the most window blocks it can ever hold at
    # once (the engine's bound: what a window layer reads plus one chunk),
    # for as long as it lives; the promises never pass the space, so an
    # allocation there cannot fail and nothing is preempted for it
    def window_fits(self, need: int) -> bool:
        return self.window_promised + need <= self.window.num_blocks

    def promise_window(self, rid: int, need: int):
        self.window_need[rid] = need
        self.window_promised += need

    def headroom(self, rid: int = None) -> int:
        """Free blocks net of OTHER requests' standing reservations."""
        others = self.reserved - (self.resv.get(rid, 0) if rid is not None
                                  else 0)
        return self.free_blocks - max(0, others)

    # --------------------------------------------------- memory forensics
    def record_stall(self, need: int, slots_short: bool = False):
        """An admission was blocked at the headroom gate — attribute the
        missing blocks to the ledger state holding them."""
        self.ledger.record_stall(need, slots_short=slots_short)

    def take_peak(self, rid) -> int:
        """Pop the request's lifetime peak live-block count."""
        return self.ledger.take_peak(rid)

    def reconcile(self) -> dict:
        """Block-for-block walk of the manager vs the ledger mirrors
        (the per-tick invariant the chaos suites assert)."""
        return self.ledger.reconcile(self.mgr, reserved=self.reserved)

    # ----------------------------------------------------------- hygiene
    def assert_quiescent(self):
        """Every block back in the pool (prefix-cache parked blocks count
        — they are reclaimable), no standing reservations, no tables.
        Failure messages carry the ledger's state breakdown (which states
        hold the leaked blocks) and land in the flight ring."""
        try:
            assert self.mgr.free_blocks == self.mgr.num_blocks, (
                f"block leak: {self.mgr.num_blocks - self.mgr.free_blocks} "
                f"of {self.mgr.num_blocks} blocks unaccounted for")
            assert self.reserved == 0, f"reservation leak: {self.reserved}"
            assert not self.resv and not self.need, (
                f"ledger leak: resv={self.resv} need={self.need}")
            assert not self.mgr.tables, f"table leak: {list(self.mgr.tables)}"
            if self.window is not None:
                w = self.window
                assert w.free_blocks == w.num_blocks and not w.tables, (
                    f"window-space leak: {w.num_blocks - w.free_blocks} of "
                    f"{w.num_blocks} blocks, tables {list(w.tables)}")
                assert not self.window_promised and not self.window_need, (
                    f"window-space promise leak: {self.window_need}")
            if self.stateful:
                held = self.mgr.snapshot_audit()["reserved"]
                assert not held, f"state-snapshot reservation leak: {held}"
        except AssertionError as e:
            FLIGHT.record("serving.quiescence_violation",
                          **self.ledger.flight_fields())
            raise AssertionError(
                f"{e} | kv ledger: {self.ledger.describe()}") from None

    def push_prefix_metrics(self):
        """Counters are process-global and cumulative; the manager's
        stats are per-engine — push only what this engine added since
        the last refresh."""
        stats = getattr(self.mgr, "cache_stats", None)
        if stats is None:
            return
        # stat keys added after construction (the radix trie grows the
        # dict) must delta against 0, not KeyError against the snapshot
        pushed = self._prefix_pushed

        def delta(key):
            return stats.get(key, 0) - pushed.get(key, 0)

        _PREFIX_HITS.inc(delta("hit_blocks"))
        _PREFIX_EVICTIONS.inc(delta("evictions"))
        _PREFIX_TOKEN_HITS.inc(delta("token_hits"))
        _PREFIX_PARTIAL_HITS.inc(delta("partial_hits"))
        if self.stateful:
            for event in ("taken", "restored", "evicted", "dropped"):
                _STATE_SNAPSHOTS.inc(delta("snap_" + event), event=event)
        self._prefix_pushed = dict(stats)
        _PREFIX_HIT_RATE.set(stats.get("hit_blocks", 0)
                             / max(stats.get("lookup_blocks", 0), 1))
        if stats.get("lookup_tokens", 0):
            _PREFIX_TOKEN_HIT_RATE.set(stats["token_hits"]
                                       / stats["lookup_tokens"])
