"""ModelExecutor: the device half of the decomposed engine (ISSUE 7).

Owns the paged KV cache, the draft-model dense cache, the engine PRNG
key, and every jitted program the tick runs — slot-aware prefill, the
chunked-prefill/verify forwards, the fused decode tick, beam-group
cache updates, and row sampling. Callers hand in fixed-shape numpy
staging arrays and get logits/tokens back; all cache donation happens
inside this class, so an exception raised BEFORE a call here leaves
``self.cache`` intact (the exception-atomicity contract the chaos
sites rely on).

The three forwards every tick runs (``tick``, ``prefill``, ``chunk``) are
handed their staging arrays as ONE fresh int32 vector
(``models.paged.Staging``: packed here, before the donating call, taken
apart by the program's first lines), because every numpy argument of a
jitted call is a transfer of its own with the device idle behind it.
``exe.dispatch`` counts a call's host arrays as ``uploads``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.decoding import KVCache, _sample_rows
from paddle_tpu.models.paged import (LATENT_LAYER, PagedKVCache,
                                     _BEAM_GROUP_UPDATE_JIT,
                                     _PREFILL_CHUNK_JIT, _PREFILL_JIT,
                                     _PREFIX_COW_JIT, _REWIND_LENS_JIT,
                                     _STATE_RESTORE_JIT, _STATE_TAKE_JIT,
                                     _TICK_JIT, _VERIFY_CHUNK_JIT,
                                     _async_tick_jit, _backbone,
                                     _prefix_cow_update, counts_routed,
                                     init_states, kv_windows, layer_kinds,
                                     llama_verify_chunk_paged,
                                     prefill_chunk_staged, prefill_staged,
                                     prefill_staging, spec_rewind_lens,
                                     tick_staged, tick_staging)
from paddle_tpu.models.speculative import _FWD_ROWS_JIT
from paddle_tpu.observability import span as _span

# module-level so its compile cache persists across admissions
_SAMPLE_ROWS_JIT = jax.jit(_sample_rows, static_argnums=(4,))
# the engine key's split as ONE dispatch (split, then unpacked on the host,
# is three); the two keys are the same bits
_SPLIT_JIT = jax.jit(lambda key: tuple(jax.random.split(key)))


class _ById:
    """Static data compared by identity: one object for each distinct
    treedef (``_KEYS``), so engines over models of one structure share
    their traced programs as before."""

    def __init__(self, treedef):
        self.treedef = treedef

    __hash__ = object.__hash__


_KEYS: dict = {}          # treedef -> its _ById


class _FlatModel:
    """The model as the jitted programs are handed it: flattened once.

    Every call of a jitted program walks its arguments' pytrees. For a
    ``Module`` tree that walk is a Python flatten and a Python comparison
    of static data for every module: 48 layers of six modules cost about
    as much as the rest of a decode tick's dispatch together, every tick,
    with the device idle meanwhile. This node's children are the model's
    leaves and its static data the model's treedef, compared by identity;
    unflattening gives the ``Module`` back, so a traced program sees the
    model it always saw and traces as before. The weights are read when
    the executor is built: to serve other weights, build another."""

    def __init__(self, model):
        self.leaves, treedef = jax.tree_util.tree_flatten(model)
        self.key = _KEYS.setdefault(treedef, _ById(treedef))


jax.tree_util.register_pytree_node(
    _FlatModel, lambda m: (m.leaves, m.key),
    lambda key, leaves: jax.tree_util.tree_unflatten(key.treedef, leaves))


def _token_rows(ids, lens) -> dict:
    """What a prefill program is sent, counted at its entry: ``rows``
    token-rows in the padded batch, ``useful`` of them a prompt token."""
    return {"rows": int(np.size(ids)), "useful": int(np.sum(lens))}


# how a refusal names a model with recurrent layers (``LLMEngine._refuse``)
STATEFUL_MODEL = "a model with recurrent (linear-attention) layers"
# and one whose layers keep latent rows (multi-head latent attention)
LATENT_MODEL = "a model with latent-attention (MLA) layers"
# and one with window layers beside full ones (two block spaces)
MIXED_MODEL = "a model with window (sliding_attention) layers beside full ones"


def _chunk_kv_blocks(lens, offs, block_size, window=None) -> int:
    """Pool blocks a cache layer the chunk kernel walks in one call: every
    live row's blocks up to the end of its chunk (in a layer of ``window``,
    from the block of the first query's window on), from the host's lengths
    (``serving.decode`` carries the same count for the decode kernel)."""
    lens, offs = np.asarray(lens), np.asarray(offs)
    live = lens > 0
    blocks = -(-(offs + lens)[live] // block_size)
    if window is not None:
        blocks = blocks - np.maximum(offs[live] - window + 1, 0) // block_size
    return int(np.sum(blocks))


class ModelExecutor:
    """Jitted prefill/decode/verify programs over one paged KV pool.

    ``cp > 1`` (context parallelism, ISSUE 18) shards the pool's physical
    blocks over a ``cp`` mesh axis — member s owns GLOBAL block ids
    [s*per, (s+1)*per), per = num_blocks/cp — while weights, block
    tables, lens and every activation stay replicated. All jitted
    programs then run inside ``shard_map``: scatters drop non-owned
    writes, attention emits per-shard online-softmax partials, and the
    merges (psum for decode, ring/Ulysses for chunk prefill) are
    bit-identical on every member, so sampling stays replicated and the
    host engine sees the exact single-device contract."""

    def __init__(self, model, *, num_slots, num_blocks, block_size,
                 max_blocks_per_seq, top_k=None, seed=0, draft_model=None,
                 spec_k=4, max_seq_len=None, kv_dtype=None, cp=1,
                 num_state_snapshots=0, window_blocks=None):
        cfg = model.cfg
        # the one copy of the model the executor keeps, and what every
        # program is handed: the weights as they were when it was built
        self._model = _FlatModel(model)
        # the running count of programs dispatched (``exe.dispatch``'s
        # ``seq``), and the count at the newest one that was not the key's
        # split: the ``seq`` a ``device_wait`` span names when it waits for
        # the program just sent
        self.seq = self.model_seq = 0
        self.top_k = top_k
        self.rng = jax.random.PRNGKey(seed)     # the setter: no pair held
        self.cp = int(cp)
        self.mesh = None
        # kv_dtype="int8": int8 block pools + parallel per-(position,
        # kv-head) f32 scale pools; every jit here quantizes on write and
        # dequantizes on read (ISSUE 17). None = pools in the model dtype.
        self.cache = PagedKVCache.init_for(
            cfg, num_blocks, block_size, num_slots, max_blocks_per_seq,
            kv_dtype=kv_dtype, window_blocks=window_blocks)
        # a cache with two block spaces (window layers beside full ones):
        # its programs are staged the window space's tables too, and the
        # chunk spans count each space's blocks with ``window``
        self.two_spaces = bool(self.cache.window_layers)
        self.window = next((w for w in kv_windows(cfg) if w is not None),
                           None) if self.two_spaces else None
        self._tick_staging = tick_staging(num_slots, self.two_spaces)
        # on every program span, so a reader need not know the family:
        # passes over the stack a token, and the K/V layers it keeps
        self.span_args = {"ut_steps": self.cache.passes,
                        "cache_layers": self.cache.cache_layers}
        # a model with recurrent layers: its state a slot is in the cache
        # (``cache.states``), donated through the programs with the pools;
        # the snapshot pool is beside it, laid out the same, a row an entry,
        # and touched by the two copy programs alone
        self.state_layers = len(self.cache.states)
        # a model whose expert layers say what they routed (``models/
        # kimi_k2.py``): a program's two counts come back with what the
        # host fetches anyway; the prefill calls' wait here, (program, seq,
        # device counts), for the next wait (``take_routed``)
        self.latent = LATENT_LAYER in (layer_kinds(cfg) or ())
        self.routes = any(map(counts_routed, _backbone(model).layers))
        self._routed = []
        self.snaps = ()
        if self.state_layers:
            self.span_args["state_layers"] = self.state_layers
            if num_state_snapshots:
                self.snaps = init_states(cfg, int(num_state_snapshots),
                                         self.state_layers)
        if self.cp > 1:
            self._init_cp(num_blocks)
        self.draft_model = draft_model
        self._draft_cache = None
        if draft_model is not None:
            dcfg = draft_model.cfg
            self._draft_cache = KVCache.init(
                dcfg.num_hidden_layers, num_slots,
                max_seq_len + spec_k + 2,
                dcfg.num_key_value_heads,
                dcfg.hidden_size // dcfg.num_attention_heads, dcfg.dtype)

    # ------------------------------------------------- context parallelism
    def _init_cp(self, num_blocks):
        """Build the cp mesh, lay the pools out sharded on their block
        axis, and compile per-executor shard_map'd twins of every cache
        program. Per-executor (not module-level) jits: their traces bake
        the mesh + PT_CP_IMPL, and they die with the executor, so the
        ``clear_jit_caches`` env-flip contract is construction-scoped for
        free."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed.mesh import HybridMesh

        cp = self.cp
        devs = jax.devices()
        if cp > len(devs):
            raise ValueError(f"cp={cp} exceeds {len(devs)} devices")
        if num_blocks % cp:
            raise ValueError(
                f"num_blocks={num_blocks} must divide by cp={cp} "
                "(equal per-shard pools)")
        self.mesh = HybridMesh(cp=cp, devices=devs[:cp])
        pool_s = NamedSharding(self.mesh.mesh, P("cp"))
        rep_s = NamedSharding(self.mesh.mesh, P())
        c = self.cache
        self.cache = PagedKVCache(
            [jax.device_put(p, pool_s) for p in c.k_pools],
            [jax.device_put(p, pool_s) for p in c.v_pools],
            jax.device_put(c.block_tables, rep_s),
            jax.device_put(c.lens, rep_s),
            tuple(jax.device_put(p, pool_s) for p in c.k_scales),
            tuple(jax.device_put(p, pool_s) for p in c.v_scales), c.passes)
        # pytree-PREFIX spec: each field leaf broadcasts over its subtree
        cs = PagedKVCache(P("cp"), P("cp"), P(), P(), P("cp"), P("cp"))
        R = P()

        def smap(fn, in_specs, out_specs):
            # check_vma off: the cross-shard merge leaves logits equal on
            # every shard, which the checker cannot infer, and the Pallas
            # calls in the body carry no varying-axes annotation
            return shard_map(fn, mesh=self.mesh.mesh,
                             in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)

        def staged_twin(program):
            """(model, staged, cache, layout) -> (logits, cache, None): no
            model that counts its routing is served under cp"""
            def twin(model, staged, cache, layout):
                return (*smap(
                    lambda *a: program(*a, layout=layout, cp_axis="cp")[:2],
                    (R, R, cs), (R, cs))(model, staged, cache), None)
            return jax.jit(twin, static_argnums=(3,), donate_argnums=(2,))

        self._cp_prefill = staged_twin(prefill_staged)
        self._cp_prefill_chunk = staged_twin(prefill_chunk_staged)
        self._cp_verify_chunk = jax.jit(smap(
            functools.partial(llama_verify_chunk_paged, cp_axis="cp"),
            (R, R, R, R, cs, R, R), (R, cs)), donate_argnums=(4,))
        self._cp_rewind = jax.jit(smap(
            spec_rewind_lens, (cs, R, R), cs), donate_argnums=(0,))
        top_k, layout = self.top_k, self._tick_staging

        # top_k / want_logp are STATIC in the tick; bake them (beams — the
        # only want_logp consumer — are refused under cp by the engine) so
        # shard_map sees purely positional array args
        def _tick(model, staged, cache, rng, bias):
            return tick_staged(model, staged, cache, rng, layout, top_k,
                               False, None, bias, cp_axis="cp")

        self._cp_tick = jax.jit(smap(
            _tick, (R, R, cs, R, R), (R, R, cs)), donate_argnums=(2,))
        self._cp_cow = jax.jit(smap(
            functools.partial(_prefix_cow_update, cp_axis="cp"),
            (cs, R, R), cs), donate_argnums=(0,))

    def _dispatch(self, program: str, jitted, *args, **kw):
        """Every jitted call the executor makes: the span ``exe.dispatch``
        (``cat="dispatch"``) around the call and nothing else, so its
        duration is the flatten, the upload and the enqueue; ``seq`` counts
        the programs, ``uploads`` the host (numpy) arrays among the call's
        arguments, each a transfer the call makes before it returns. With
        the ``seq`` on the ``device_wait`` spans a reader knows from the
        host's clock when nothing was in flight."""
        self.seq += 1
        if program != "split":
            self.model_seq = self.seq
        with _span("exe.dispatch", cat="dispatch", program=program,
                   seq=self.seq) as edge:
            if edge.recording:
                edge.set(uploads=sum(
                    isinstance(a, (np.ndarray, np.generic))
                    for a in (*args, *kw.values())))
            return jitted(*args, **kw)

    @property
    def rng(self):
        """The engine key: what the next :meth:`next_key` splits."""
        return self._rng

    @rng.setter
    def rng(self, key):
        # another key (the async window's rewind, a test): a pair split
        # ahead from the old one is not its split
        self._rng, self._split_ahead = key, None

    def next_key(self):
        """The chained split: ``rng, sub = split(rng)``. The pair is the
        one :meth:`split_ahead` dispatched, where it did."""
        pair = self._split_ahead or self._dispatch("split", _SPLIT_JIT,
                                                   self._rng)
        self._rng, sub = pair
        self._split_ahead = None
        return sub

    def split_ahead(self):
        """Dispatch the next :meth:`next_key`'s split now: called with a
        program just queued, so the split's dispatch costs the host time
        it would spend waiting, not time the device then idles."""
        if self._split_ahead is None:
            self._split_ahead = self._dispatch("split", _SPLIT_JIT,
                                               self._rng)

    def _no_cp_lora(self, lora):
        if lora is not None and self.cp > 1:
            raise NotImplementedError(
                "multi-LoRA under context parallelism (cp > 1) is not "
                "supported yet — serve adapters with cp=1")
        return lora

    # ------------------------------------------------------------ prefill
    def prefill(self, ids, lens, slots, rows, lora=None, wrows=()):
        """Slot-aware padded prefill: admitted prompts scattered into
        their cache slots while other slots keep decoding state.
        ``lora`` (optional pytree, see ``models.paged._lora_delta``)
        applies the batched multi-LoRA correction per row. The cache is
        donated, as in the chunk program: a tick's several calls in
        flight hold one pool, not one more for each. ``wrows``, here and in
        the chunk call: ``(the rows' window-space tables,)`` for a cache
        with two block spaces."""
        with _span("exe.prefill", **_token_rows(ids, lens),
                   **self._ctx_tokens(lens, 0), **self.span_args):
            layout = prefill_staging(*np.shape(ids), np.shape(rows)[1],
                                     False, self.two_spaces)
            staged = layout.pack(ids, lens, slots, rows, *wrows)
            if self.cp > 1:
                self._no_cp_lora(lora)
                logits, self.cache, _ = self._dispatch(
                    "prefill", self._cp_prefill, self._model, staged,
                    self.cache, layout)
                return logits
            logits, self.cache, routed = self._dispatch(
                "prefill", _PREFILL_JIT, self._model, staged, self.cache,
                layout, lora=lora)
            if routed is not None:
                self._routed.append(("prefill", self.seq, routed))
            return logits

    def prefill_chunk(self, ids, lens, offs, slots, rows, lora=None,
                      wrows=()):
        """One chunk per row, written from an arbitrary offset over the
        slot's pool prefix (chunked prefill / prefix-cache resume)."""
        with _span("exe.prefill_chunk", **_token_rows(ids, lens),
                   kv_blocks=_chunk_kv_blocks(lens, offs,
                                              self.cache.block_size),
                   **self._space_blocks(lens, offs),
                   **self._ctx_tokens(lens, offs), **self.span_args):
            layout = prefill_staging(*np.shape(ids), np.shape(rows)[1],
                                     True, self.two_spaces)
            staged = layout.pack(ids, lens, offs, slots, rows, *wrows)
            if self.cp > 1:
                self._no_cp_lora(lora)
                logits, self.cache, _ = self._dispatch(
                    "chunk", self._cp_prefill_chunk, self._model, staged,
                    self.cache, layout)
                return logits
            logits, self.cache, routed = self._dispatch(
                "chunk", _PREFILL_CHUNK_JIT, self._model, staged,
                self.cache, layout, lora=lora)
            if routed is not None:
                self._routed.append(("chunk", self.seq, routed))
            return logits

    def take_routed(self):
        """What the prefill calls since the last wait routed, one span
        ``exe.routed`` a call (``program``, the call's ``seq``,
        ``routed_pairs``, ``experts_hit``): a prefill call's own span
        closes when it is queued, before the device has counted. Called
        where the host has just waited for a later program, so the counts
        are there to read; read only while spans record."""
        for program, seq, counts in self._routed:
            with _span("exe.routed", program=program, seq=seq) as sp:
                if sp.recording:
                    pairs, hit = np.asarray(counts).tolist()
                    sp.set(routed_pairs=pairs, experts_hit=hit)
        self._routed.clear()

    def _space_blocks(self, lens, offs) -> dict:
        """``kv_blocks_full`` and ``kv_blocks_window`` of a chunk call on a
        cache with two block spaces: the blocks a layer of each space walks
        for the call's live rows. Nothing for any other cache."""
        if not self.two_spaces:
            return {}
        bs = self.cache.block_size
        return {"kv_blocks_full": _chunk_kv_blocks(lens, offs, bs),
                "kv_blocks_window": _chunk_kv_blocks(lens, offs, bs,
                                                     self.window)}

    def _ctx_tokens(self, lens, offs) -> dict:
        """``ctx_tokens`` of a prefill call for a model with recurrent or
        latent layers or two block spaces: the sum over its live rows of
        ``offset + len``, from the host's lengths (with ``useful``, each
        token's context for a count of the call's FLOPs). Nothing, and no
        work, for any other model."""
        if not (self.state_layers or self.latent or self.two_spaces):
            return {}
        lens = np.asarray(lens)
        return {"ctx_tokens": int(np.sum((np.asarray(offs) + lens)[lens > 0]))}

    def state_slots(self, n_run: int) -> dict:
        """``state_slots`` of a decode tick's spans: the running slots
        whose recurrent state the tick reads and writes. Nothing for a
        model whose every layer keeps K/V."""
        return {"state_slots": n_run} if self.state_layers else {}

    def take_state(self, slot: int, idx: int):
        """Snapshot entry ``idx`` <- the state slot ``slot`` holds once the
        programs queued so far have run."""
        self.snaps = self._dispatch(
            "state_take", _STATE_TAKE_JIT, self.snaps, self.cache.states,
            np.int32(slot), np.int32(idx))

    def restore_state(self, slot: int, idx: int):
        """Slot ``slot``'s state <- snapshot entry ``idx``."""
        self.cache = self._dispatch(
            "state_restore", _STATE_RESTORE_JIT, self.cache, self.snaps,
            np.int32(slot), np.int32(idx))

    def verify_chunk(self, ids, clens, offs, slot_ids, rows, lora=None):
        """Target forward over each slot's proposal window (spec decode);
        shares the chunked-prefill program shape."""
        if self.state_layers:
            raise NotImplementedError(
                f"{STATEFUL_MODEL} is not served with verify_chunk: a "
                "rejected token's write to the recurrent state cannot be "
                "rolled back")
        if self.latent:
            raise NotImplementedError(
                f"{LATENT_MODEL} is not served with verify_chunk: no test "
                "has rewound a latent pool past a rejected proposal")
        if self.two_spaces:
            raise NotImplementedError(
                f"{MIXED_MODEL} is not served with verify_chunk: the "
                "verify program is handed one space's tables")
        ids, clens, offs = (jnp.asarray(ids), jnp.asarray(clens),
                            jnp.asarray(offs))
        slot_ids, rows = jnp.asarray(slot_ids), jnp.asarray(rows)
        if self.cp > 1:
            self._no_cp_lora(lora)
            logits, self.cache = self._dispatch(
                "verify", self._cp_verify_chunk, self._model, ids, clens,
                offs, self.cache, slot_ids, rows)
            return logits
        logits, self.cache = self._dispatch(
            "verify", _VERIFY_CHUNK_JIT, self._model, ids, clens, offs,
            self.cache, slot_ids, rows, lora=lora)
        return logits

    def rewind_lens(self, slots, lens):
        """Length-pointer-only rewind after a partial spec accept."""
        self.cache = self._dispatch(
            "rewind", self._cp_rewind if self.cp > 1 else _REWIND_LENS_JIT,
            self.cache, jnp.asarray(slots), jnp.asarray(lens))

    # ------------------------------------------------------------- decode
    def decode_tick(self, last_tok, run_mask, rows, cols, vals, temps,
                    top_ps, need_logp, lora=None, bias=None, wvals=()):
        """The fused one-token tick: incremental table update + paged
        attention + on-device sampling. Returns (sampled [num_slots],
        logp [num_slots, vocab] or None per ``need_logp``). ``lora`` is
        the per-slot multi-LoRA pytree; ``bias`` a [num_slots, V]
        grammar-mask logit bias applied before sampling; ``wvals`` ``(the
        window table's new entries,)`` for a cache with two block
        spaces."""
        n_run = int(np.sum(run_mask))
        with _span("exe.decode_tick", slots=n_run, **self.span_args,
                   **self.state_slots(n_run)):
            sub = self.next_key()
            # one fresh vector, one transfer, made by the call: a numpy
            # argument each is a transfer each, and a ``jnp.asarray`` each
            # a dispatch each, with the device idle meanwhile
            staged = self._tick_staging.pack(last_tok, run_mask, rows, cols,
                                             vals, temps, top_ps, *wvals)
            if self.cp > 1:
                self._no_cp_lora(lora)
                if need_logp:
                    raise NotImplementedError(
                        "beam search (want_logp) under cp > 1 is not "
                        "supported")
                nxt, logp, self.cache = self._dispatch(
                    "tick", self._cp_tick, self._model, staged, self.cache,
                    sub, bias)
            else:
                nxt, logp, self.cache = self._dispatch(
                    "tick", _TICK_JIT, self._model, staged, self.cache, sub,
                    self._tick_staging, self.top_k, need_logp,
                    lora=lora, logit_bias=bias)
        self.split_ahead()       # the tick is queued: the next key's split
        return nxt, logp

    def decode_tick_async(self, tokens, active, stop, gen, max_gen,
                          temps, top_ps, eos_id):
        """Depth-K pipelined tick (ISSUE 20): ``tokens``/``stop``/``gen``
        are DEVICE arrays threaded from the previous call (``active`` is
        the host's mask) — the sampled
        token array feeds the next call without a host round trip, and
        EOS/max-gen stop is evaluated in the jit via the stop mask. No
        table updates, grammar bias, LoRA, or beam logp: the engine
        drains its window and takes :meth:`decode_tick` for any tick
        needing them. Returns (nxt, ran, stop', gen'), all on device."""
        with _span("exe.decode_tick", slots=int(np.sum(active)),
                   **self.span_args):
            sub = self.next_key()
            nxt, ran, stop, gen, self.cache = self._dispatch(
                "tick", _async_tick_jit(),
                self._model, tokens, self.cache, jnp.asarray(active), stop,
                gen, max_gen, sub, jnp.asarray(temps), jnp.asarray(top_ps),
                jnp.int32(eos_id), self.top_k)
            return nxt, ran, stop, gen

    def apply_block_copies(self, pairs):
        """Radix prefix cache COW plan: copy each (src, dst) pool block
        before this tick's programs write the pool. Padded to a fixed
        width so the jit compiles once; longer plans run in batches."""
        nb = self.cache.num_blocks
        width = 8
        cow = self._cp_cow if self.cp > 1 else _PREFIX_COW_JIT
        for i in range(0, len(pairs), width):
            chunk = pairs[i:i + width]
            src = np.full(width, nb, np.int32)      # sentinel = no copy
            dst = np.full(width, nb, np.int32)
            for j, (s, d) in enumerate(chunk):
                src[j], dst[j] = s, d
            self.cache = self._dispatch("cow", cow, self.cache,
                                        jnp.asarray(src), jnp.asarray(dst))

    def beam_group_update(self, slots, rows, lens_val, copy_src, copy_dst):
        """Install forked beam tables + partial-block copy-on-write."""
        if self.cp > 1:
            raise NotImplementedError(
                "beam search under context parallelism (cp > 1) is not "
                "supported yet")
        self.cache = self._dispatch(
            "beam", _BEAM_GROUP_UPDATE_JIT,
            self.cache, jnp.asarray(slots, jnp.int32), jnp.asarray(rows),
            jnp.asarray(lens_val, jnp.int32), jnp.asarray(copy_src),
            jnp.asarray(copy_dst))

    # ------------------------------------------------------------- sample
    def sample_rows(self, logits, temps, top_ps, bias=None):
        """Per-row temperature/top-k/top-p sampling, dispatched and not
        waited for: -> the rows' tokens, on the device. ``bias`` ([rows,
        V], 0 / -1e30) is the grammar-mask addend."""
        sampled = self._dispatch(
            "sample", _SAMPLE_ROWS_JIT,
            logits.astype(jnp.float32), self.next_key(), jnp.asarray(temps),
            jnp.asarray(top_ps), self.top_k,
            bias=(None if bias is None else jnp.asarray(bias)))
        self.split_ahead()       # queued behind the rows' forward
        return sampled

    def fetch_sampled(self, sampled):
        """The host's one wait of a prefill entry: each of
        :meth:`sample_rows`' results, as numpy."""
        with _span("exe.sample", cat="device_wait", seq=self.model_seq,
                   rows=sum(t.shape[0] for t in sampled)):
            got = [np.asarray(t) for t in sampled]
        if self._routed:
            self.take_routed()
        return got

    # -------------------------------------------------------------- draft
    def draft_rows(self, ids, rp, cl):
        """One draft-model forward over per-row chunks of the dense
        draft cache (speculative proposal feeds)."""
        logits, self._draft_cache = self._dispatch(
            "draft", _FWD_ROWS_JIT,
            self.draft_model, jnp.asarray(ids), self._draft_cache,
            jnp.asarray(rp, jnp.int32), None, jnp.asarray(cl, jnp.int32))
        return logits
