"""Paged KV cache + continuous batched decode (serving story).

Ref capability: PaddleNLP ``llm`` predictor block-attention +
``fused_multi_transformer_op.cu``'s block KV cache. TPU-native split of
responsibilities:

  * DEVICE: fixed-shape jitted steps — ``llama_prefill_paged`` (padded
    ragged prompts through the varlen flash path, K/V scattered into the
    block pool) and ``llama_decode_step_paged`` (one token per sequence,
    pool-direct paged attention via the scalar-prefetch Pallas kernel).
  * HOST: ``BlockManager`` — the free-list/allocation policy (what vLLM's
    scheduler does). Between steps it grows block tables and recycles a
    finished sequence's blocks. Host-side management is the TPU-idiomatic
    design: allocation is control flow, not math, and the device program
    keeps a single static shape.

HBM for the cache is ``num_blocks * block_size`` tokens ≈ Σ actual sequence
lengths (rounded up per block) — NOT batch × max_len as in the static
``KVCache`` (models/decoding.py), which this complements, not replaces.
"""
from __future__ import annotations

import contextlib
import functools
import os
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observability.memledger import MemLedger
from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas.latent_attention import (
    _latent_chunk_call, latent_row_width, paged_latent_chunk_attention,
    paged_latent_decode_attention)
from paddle_tpu.ops.pallas.paged_attention import (_note_trace,
                                                   _paged_chunk_call,
                                                   paged_chunk_attention,
                                                   paged_decode_attention)
from paddle_tpu.quantization import wo_matmul as _wo


@dataclass
class PagedKVCache:
    """Per-layer block pools + per-sequence block tables (pytree).

    ``k_scales``/``v_scales`` are EMPTY for the bf16 pool (the legacy
    4-arg construction still works) and hold per-layer
    [N_blocks, block_size, H_kv] f32 scale pools when the KV pool is
    int8 (``init(..., kv_dtype="int8")``): element (n, o, h) is the
    absmax/127 scale of pool row (n, o, h, :). Tuple truthiness is
    STATIC pytree structure, so jitted forwards branch on
    ``if cache.k_scales:`` at trace time — the bf16 trace is unchanged.

    ``passes`` (static) is how often a token runs the whole layer stack
    (``cache_passes(cfg)``: a looped model's ``total_ut_steps``, else 1).
    Every (pass, layer) pair keeps K/V of its own: layer ``li``'s pool
    holds ``passes * N_blocks`` rows, pass ``u``'s copy of block ``b`` at
    row ``u * N_blocks + b`` (``_pass_tables``). Block ids, tables and the
    host's managers stay per-block: one block is one span of tokens in
    every cache layer.

    ``states`` is empty for a model whose every layer keeps K/V. A model
    with recurrent layers (``layer_kinds(cfg)``: the gated delta rule of
    ``models/olmo_hybrid.py``) holds K/V pools for its full layers only,
    and for each linear layer a pair ``(S [slots, H, d_k, d_v] float32,
    conv [slots, K - 1, channels])``: the state a slot's tokens have left
    there, beside the K/V they left in the pools.

    A model whose layers keep LATENT rows (multi-head latent attention,
    ``models/kimi_k2.py``; ``layer_kinds`` names them ``latent_attention``)
    holds ONE pool a layer, not a K pool and a V pool: ``k_pools[i]`` is
    ``[N_blocks, block_size, W]``, a position's row the normalised latent
    and the one rotated key all heads share, side by side
    (``latent_row_width``: whole 128-lane rows, the lanes past the two
    zero), and ``v_pools`` is empty. Block ids, tables, the trie,
    copy-on-write and the scheduler are per block as for K/V, so a prefix
    hit adopts latent blocks as it adopts K/V blocks.

    A model with WINDOW layers beside full ones (``models/trinity.py``;
    ``window_space_layers``) keeps TWO BLOCK SPACES in one cache: the pools
    of the full layers hold ``N_blocks`` blocks and are addressed by
    ``block_tables``; the pools of the window layers (``window_layers``,
    static: their indices among the K/V layers) hold ``window_blocks``
    blocks, of a numbering of their own, and are addressed by
    ``window_tables``. Both tables are indexed by ``position //
    block_size``; a window layer reads only a row's last ``window``
    positions, so the host frees the window space's blocks below them and a
    row holds O(window) blocks there, O(length) in the full space. A model
    of one kind has no second space: ``window_tables`` None,
    ``window_layers`` ()."""
    k_pools: list   # [L] of [passes * N_blocks, block_size, H_kv, D]
    v_pools: list   # (latent layers: k_pools [N_blocks, block_size, W], no v)
    block_tables: jnp.ndarray  # [B, max_blocks] int32 (pad = n_blocks)
    lens: jnp.ndarray          # [B] int32 — tokens currently in cache
    k_scales: tuple = ()       # [L] of [passes * N_blocks, block_size, H_kv] f32
    v_scales: tuple = ()
    passes: int = 1
    states: tuple = ()         # [linear layers] of (S, conv), a row a slot
    window_tables: jnp.ndarray | None = None   # [B, max_blocks], pad = N_w
    window_layers: tuple = ()  # K/V layers whose pools are the window space

    @property
    def block_size(self):
        return self.k_pools[0].shape[1]

    @property
    def pool_rows(self):
        """Rows of one pool (of the full space, where there are two):
        every pass's copy of every block."""
        full = next(i for i in range(len(self.k_pools))
                    if i not in self.window_layers)
        return self.k_pools[full].shape[0]

    @property
    def window_blocks(self):
        """Blocks of the window space (0: the cache has one space)."""
        return (self.k_pools[self.window_layers[0]].shape[0]
                if self.window_layers else 0)

    @property
    def num_blocks(self):
        return self.pool_rows // self.passes

    @property
    def cache_layers(self):
        """K/V layers a token keeps: one a (pass, layer) pair."""
        return len(self.k_pools) * self.passes

    def pool_tokens(self):
        """Total cache capacity in tokens (the HBM bound)."""
        return self.num_blocks * self.block_size

    @staticmethod
    def init(num_layers, num_blocks, block_size, num_kv_heads, head_dim,
             batch, max_blocks_per_seq, dtype, kv_dtype=None, passes=1):
        pool_dtype = dtype
        rows = passes * num_blocks
        k_scales = v_scales = ()
        if kv_dtype is not None:
            if jnp.dtype(kv_dtype) != jnp.int8:
                raise ValueError(
                    f"unsupported kv_dtype {kv_dtype!r}: only 'int8' "
                    "(per-position absmax scales) or None (model dtype)")
            pool_dtype = jnp.int8
            zs = lambda: jnp.zeros((rows, block_size, num_kv_heads),
                                   jnp.float32)
            k_scales = tuple(zs() for _ in range(num_layers))
            v_scales = tuple(zs() for _ in range(num_layers))
        z = lambda: jnp.zeros((rows, block_size, num_kv_heads,
                               head_dim), pool_dtype)
        return PagedKVCache(
            [z() for _ in range(num_layers)],
            [z() for _ in range(num_layers)],
            jnp.full((batch, max_blocks_per_seq), num_blocks, jnp.int32),
            jnp.zeros((batch,), jnp.int32), k_scales, v_scales, passes)

    @staticmethod
    def init_for(cfg, num_blocks, block_size, batch, max_blocks_per_seq,
                 kv_dtype=None, window_blocks=None):
        """The cache of the model ``cfg`` describes: its layers, its
        passes over them, its K/V heads; for a model with window layers
        beside full ones its two block spaces, ``num_blocks`` of the full
        and ``window_blocks`` of the window space. A model of one kind
        builds what it always built."""
        kinds = layer_kinds(cfg)
        if kinds is not None and LATENT_LAYER in kinds:
            if set(kinds) != {LATENT_LAYER} or kv_dtype is not None \
                    or cache_passes(cfg) != 1:
                raise NotImplementedError(
                    "latent layers beside layers of another kind, under "
                    "several passes, or in a quantized pool are not built")
            width = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
            return PagedKVCache(
                [jnp.zeros((num_blocks, block_size, width), cfg.dtype)
                 for _ in kinds], [],
                jnp.full((batch, max_blocks_per_seq), num_blocks, jnp.int32),
                jnp.zeros((batch,), jnp.int32))
        n_kv = (cfg.num_hidden_layers if kinds is None
                else len(kinds) - kinds.count(LINEAR_LAYER))
        cache = PagedKVCache.init(
            n_kv, num_blocks, block_size,
            kv_pool_heads(cfg.num_key_value_heads), head_dim(cfg), batch,
            max_blocks_per_seq, cfg.dtype, kv_dtype=kv_dtype,
            passes=cache_passes(cfg))
        if kinds is not None and LINEAR_LAYER in kinds:
            cache.states = init_states(cfg, batch,
                                       kinds.count(LINEAR_LAYER))
        window = window_space_layers(cfg)
        if window:
            if kv_dtype is not None or cache.passes != 1 or cache.states \
                    or not window_blocks:
                raise NotImplementedError(
                    "two block spaces (window layers beside full ones) in "
                    "a quantized pool, under several passes, beside "
                    "recurrent layers or without window_blocks are not "
                    "built")
            shape = (int(window_blocks),) + cache.k_pools[0].shape[1:]
            for ci in window:
                cache.k_pools[ci] = jnp.zeros(shape, cfg.dtype)
                cache.v_pools[ci] = jnp.zeros(shape, cfg.dtype)
            cache.window_tables = jnp.full((batch, max_blocks_per_seq),
                                           int(window_blocks), jnp.int32)
            cache.window_layers = window
        return cache


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((c.k_pools, c.v_pools, c.block_tables, c.lens,
                c.k_scales, c.v_scales, c.states, c.window_tables),
               (c.passes, c.window_layers)),
    lambda aux, ch: PagedKVCache(*ch[:6], aux[0], ch[6], ch[7], aux[1]))


def kv_pool_heads(num_kv_heads: int) -> int:
    """K/V heads a pool row holds for a model of ``num_kv_heads``: that
    many, or, where they pass the sublane tile of 8 without filling it (30
    heads), the next multiple (32), the heads past the model's own zero.
    The paged kernels copy ``[block, H_kv, D]`` slabs of the pool where it
    lies, and Mosaic copies whole tiles of the last two dims
    (``decode_slab_is_tiled``): off the tiling a model is served by the
    gathers, which build a row's whole table width every call. The forwards
    pad a layer's heads to its pool's (``_pool_heads``), so a cache built
    by hand at the model's own head count is served as before."""
    if num_kv_heads <= 8 or num_kv_heads % 8 == 0:
        return num_kv_heads
    return -(-num_kv_heads // 8) * 8


def _pool_heads(x, heads: int):
    """[..., H, D] -> [..., ``heads``, D]: the heads a pool row has past
    the model's own are zero (their scores are never read)."""
    if x.shape[-2] == heads:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, heads - x.shape[-2])
    return jnp.pad(x, pad)


LINEAR_LAYER, FULL_LAYER = "linear_attention", "full_attention"
LATENT_LAYER = "latent_attention"
WINDOW_LAYER = "sliding_attention"


def layer_kinds(cfg):
    """A model's layers by kind, from what its configuration says of them
    (``layer_types``, one name a layer), or None where every layer keeps
    K/V alike: the one-kind model every forward here served before. The
    kinds: ``full_attention`` (K/V pools), ``sliding_attention`` (K/V pools
    of which a query reads the last ``cfg.sliding_window`` positions: the
    window is the layer's, not the model's), ``linear_attention`` (a
    recurrent state a slot, no K/V: ``PagedKVCache.states``) and
    ``latent_attention`` (one pool of latent rows a layer, read by all
    heads)."""
    kinds = getattr(cfg, "layer_types", None)
    if not kinds or not {LINEAR_LAYER, LATENT_LAYER, WINDOW_LAYER} & set(
            kinds[:cfg.num_hidden_layers]):
        return None
    return tuple(kinds[:cfg.num_hidden_layers])


def kv_windows(cfg) -> tuple:
    """The window of each layer that keeps K/V, in the order of the cache's
    pools: how many of a row's last positions a query there reads, None
    for all of them. The one answer to "is this layer windowed": a model
    that names its layers' kinds gives its ``sliding_attention`` layers
    ``cfg.sliding_window`` and its ``full_attention`` layers None; a model
    that does not (Mistral v0.1's shape) gives every layer
    ``cfg.sliding_window``."""
    window = getattr(cfg, "sliding_window", None)
    kinds = layer_kinds(cfg)
    if kinds is None:
        return (window,) * cfg.num_hidden_layers
    return tuple(window if k == WINDOW_LAYER else None for k in kinds
                 if k in (FULL_LAYER, WINDOW_LAYER))


def window_space_layers(cfg) -> tuple:
    """The K/V layers (their indices among the cache's pools) whose blocks
    live in a space of their own: the window layers of a model that has
    full layers too. () for a model of one kind, whose one space is what
    it always was, windowed or not."""
    windows = kv_windows(cfg)
    if all(w is None for w in windows) or None not in windows:
        return ()
    return tuple(i for i, w in enumerate(windows) if w is not None)


def head_dim(cfg) -> int:
    """A head's width: the configuration's own ``head_dim`` where it
    states one (it need not be hidden / heads), else hidden / heads."""
    return (getattr(cfg, "head_dim", None)
            or cfg.hidden_size // cfg.num_attention_heads)


def state_shapes(cfg):
    """(recurrent state, conv state) of one linear layer and one slot:
    ``S`` a head in ``R^{d_k x d_v}``, and the last ``K - 1`` inputs of
    the depthwise convolution over the q, k and v channels."""
    h, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    return ((h, dk, dv), (cfg.linear_conv_kernel_dim - 1, h * (2 * dk + dv)))


def init_states(cfg, rows, layers):
    """``rows`` zeroed states (a slot's, or a snapshot pool's entries) for
    each of ``layers`` linear layers: float32 ``S``, the conv inputs in
    the model's dtype."""
    s_shape, c_shape = state_shapes(cfg)
    return tuple((jnp.zeros((rows,) + s_shape, jnp.float32),
                  jnp.zeros((rows,) + c_shape, cfg.dtype))
                 for _ in range(layers))


def state_bytes(states) -> int:
    """HBM bytes ONE row (a slot, a snapshot) holds over all linear
    layers, as the arrays are logically sized."""
    return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
               for pair in states for a in pair)


def cache_passes(cfg) -> int:
    """Times a token runs the whole layer stack: a looped model's
    ``total_ut_steps``, 1 for every other."""
    return int(getattr(cfg, "total_ut_steps", 1))


def _pass_tables(tables, u, num_blocks, passes):
    """Block ids -> pool rows of pass ``u``: its copy of block ``b`` lies
    at row ``u * num_blocks + b``. The sentinel (any id >= num_blocks)
    goes past the last row, where scatters drop it and the kernels never
    read."""
    return jnp.where(tables < num_blocks, tables + u * num_blocks,
                     passes * num_blocks)


# ------------------------------------------------ int8 KV quantization
def _quantize_kv(vals):
    """Per-(position, head) symmetric int8: vals [..., H, D] ->
    (int8 [..., H, D], f32 scales [..., H]). absmax over D / 127; the
    epsilon floor keeps all-zero rows (padding) at scale ~0 without a
    0/0."""
    _note_trace("kv:int8-write")
    f = vals.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale


def _scatter_kv(pools, k, v, scatter, *args):
    """Scatter one layer's new K/V into its ``pools`` (k_pool, v_pool,
    k_scale, v_scale; the scales None for a bf16 pool) through ``scatter``
    (one of the three scatter primitives below — all are ``(pool, vals,
    *rest)`` and trailing-dim generic). bf16 pool: plain writes, scale
    slots None. int8 pool: quantize-on-write — the int8 codes land in the
    pools and the absmax scales in the parallel scale pools via the SAME
    scatter (same table/len/active masking, so codes and scales never
    desync)."""
    k_pool, v_pool, k_scale, v_scale = pools
    if k_scale is None:
        return (scatter(k_pool, k, *args), scatter(v_pool, v, *args),
                None, None)
    qk, sk = _quantize_kv(k)
    qv, sv = _quantize_kv(v)
    return (scatter(k_pool, qk, *args), scatter(v_pool, qv, *args),
            scatter(k_scale, sk, *args), scatter(v_scale, sv, *args))


class BlockManager:
    """Host-side free-list allocator for the shared block pool."""

    def __init__(self, num_blocks: int, block_size: int):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = list(range(num_blocks - 1, -1, -1))
        self.tables: dict[int, list[int]] = {}
        self._prefix_done: dict[int, int] = {}  # free_prefix resume index
        # per-pool memory ledger: every mutation choke point below
        # notifies it (test_lint enforces the list), so the five-state
        # block classification reconciles by construction
        self.ledger = MemLedger(num_blocks, block_size)

    @property
    def free_blocks(self):
        return len(self._free)

    def blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def allocate(self, seq_id: int, n_tokens: int):
        """Ensure seq_id owns enough blocks for n_tokens; grow as needed."""
        table = self.tables.setdefault(seq_id, [])
        need = self.blocks_needed(n_tokens) - len(table)
        if need > self.free_blocks:
            raise MemoryError(
                f"paged cache out of blocks: need {need}, "
                f"free {self.free_blocks} (of {self.num_blocks})")
        for _ in range(max(need, 0)):
            blk = self._pop_free()
            table.append(blk)
            self.ledger.table_enter(seq_id, blk)
        return table

    def _pop_free(self) -> int:
        """Take one block off the free list (prefix-cache eviction hook)."""
        if not self._free:
            raise MemoryError("paged cache out of blocks")
        return self._free.pop()

    def free(self, seq_id: int):
        for b in reversed(self.tables.pop(seq_id, [])):
            if b is None:
                continue
            self.ledger.table_exit(seq_id, b)
            self._free.append(b)
        self.ledger.table_drop(seq_id)
        self._prefix_done.pop(seq_id, None)

    def free_prefix(self, seq_id: int, n_blocks: int):
        """Release the first ``n_blocks`` table entries (sliding-window
        recycling: positions below ``cur - window`` are never attended
        again — ref block-attention's window cache bound). Table POSITIONS
        are kept as ``None`` placeholders so later block indices stay
        aligned; returns the freed (position, block) pairs. Scans resume
        from the last freed index, so each block is visited once over the
        sequence's whole lifetime (not O(length^2) re-walks)."""
        table = self.tables.get(seq_id, [])
        upto = min(n_blocks, len(table))
        start = self._prefix_done.get(seq_id, 0)
        freed = []
        for idx in range(start, upto):
            if table[idx] is not None:
                freed.append((idx, table[idx]))
                self.ledger.table_exit(seq_id, table[idx], hole=True)
                self._release(table[idx])
                table[idx] = None
        if upto > start:
            self._prefix_done[seq_id] = upto
        return freed

    def _release(self, blk: int):
        """Return one block to the free list (refcount hook point)."""
        self._free.append(blk)

    def table_array(self, seq_ids, max_blocks):
        """[B, max_blocks] int32; unused slots = num_blocks (OOB sentinel,
        dropped by scatter, clamped-masked by the kernel contract)."""
        out = np.full((len(seq_ids), max_blocks), self.num_blocks, np.int32)
        for row, sid in enumerate(seq_ids):
            t = self.tables.get(sid, [])
            if self._prefix_done.get(sid, 0) == 0:   # no None placeholders
                out[row, :len(t)] = t
            else:
                for idx, b in enumerate(t):
                    if b is not None:
                        out[row, idx] = b
        return jnp.asarray(out)


class RefBlockManager(BlockManager):
    """BlockManager + refcounts: beams FORK a sequence by sharing its full
    (immutable — the pool is append-only) blocks and privately copying only
    the partial last block. The reference's block-attention serving keeps
    the same share/copy split for beams (vLLM-style copy-on-write, but
    append-only KV means ONLY the tail block can ever need the copy)."""

    def __init__(self, num_blocks: int, block_size: int):
        super().__init__(num_blocks, block_size)
        self._rc: dict[int, int] = {}

    def allocate(self, seq_id, n_tokens):
        before = set(self.tables.get(seq_id, []))
        table = super().allocate(seq_id, n_tokens)
        for blk in table:
            if blk not in before:
                self._rc[blk] = 1
        return table

    def fork(self, src_id, dst_id, n_tokens: int):
        """dst shares src's blocks; if the last block is partial (n_tokens
        not block-aligned) dst gets a PRIVATE fresh block for it. Returns
        (src_blk, dst_blk) to copy on device, or None."""
        src = self.tables[src_id]
        table = list(src)
        copy = None
        partial = (n_tokens % self.block_size != 0 and table
                   and table[-1] is not None)
        if partial and not self.free_blocks:
            # capacity check BEFORE the retain loop: a failed fork must
            # leave refcounts untouched (callers retry after preempting —
            # a leaked retain would permanently shrink the pool)
            raise MemoryError("paged cache out of blocks for beam fork")
        for blk in (table[:-1] if partial else table):
            if blk is None:   # window-recycled placeholder: nothing shared
                continue
            self._retain(blk)
        # the fork inherits the recycled-prefix marker: table_array's fast
        # path and future free_prefix scans key on it
        if src_id in self._prefix_done:
            self._prefix_done[dst_id] = self._prefix_done[src_id]
        if partial:
            fresh = self._pop_free()
            self._rc[fresh] = 1
            copy = (table[-1], fresh)
            table[-1] = fresh
        self.tables[dst_id] = table
        for blk in table:
            if blk is not None:
                self.ledger.table_enter(dst_id, blk)
        return copy

    def free(self, seq_id):
        for blk in self.tables.pop(seq_id, []):
            if blk is None:
                continue
            self.ledger.table_exit(seq_id, blk)
            self._release(blk)
        self.ledger.table_drop(seq_id)
        self._prefix_done.pop(seq_id, None)

    def _release(self, blk):
        """Refcounted release: the block returns to the free list only at
        rc == 0 (free_prefix routes through here too, so windowed
        recycling can never double-free a beam-shared block)."""
        self._rc[blk] -= 1
        if self._rc[blk] == 0:
            del self._rc[blk]
            self._free.append(blk)

    def _retain(self, blk):
        """Take one more reference on a live block (beam fork sharing)."""
        self._rc[blk] = self._rc.get(blk, 0) + 1


class PrefixMatch:
    """Longest shared TOKEN span found by
    :meth:`RadixPrefixBlockManager.match_prefix`.

    ``blocks`` are fully-shared blocks (adopted rc+1, zero copies);
    ``cow`` is the optional partial boundary share — ``(src_block,
    hit_tokens)`` with ``0 < hit_tokens < block_size`` — the adopter gets
    a private copy of ``src_block`` and prefills from token ``hit``
    inside it. ``len()`` is the number of fully-shared blocks so the
    scheduler's block-denominated reservation math stays
    manager-agnostic; truthiness is any token hit at all.

    ``snapshot`` is ``(token depth, entry)`` of the deepest recurrent-state
    snapshot on the matched path, None where there is none (always, for a
    model whose every layer keeps K/V); ``offered`` the tokens the K/V
    match alone found, which ``state_hit`` keeps when it cuts the match
    down to that snapshot."""

    __slots__ = ("blocks", "token_count", "cow", "snapshot", "offered")

    def __init__(self, blocks, token_count, cow=None, snapshot=None,
                 offered=None):
        self.blocks = blocks
        self.token_count = token_count
        self.cow = cow
        self.snapshot = snapshot
        self.offered = token_count if offered is None else offered

    def __len__(self):
        return len(self.blocks)

    def __bool__(self):
        return self.token_count > 0

    def __iter__(self):
        return iter(self.blocks)

    def __repr__(self):
        return (f"PrefixMatch(blocks={self.blocks}, "
                f"token_count={self.token_count}, cow={self.cow})")


class _RadixNode:
    """One radix-trie edge: a token span owning the physical blocks that
    hold its KV. Spans start block-aligned; only a childless tail may be
    partial (len(tokens) % block_size != 0)."""

    __slots__ = ("tokens", "blocks", "children", "parent", "touch", "snaps")

    def __init__(self, tokens, blocks, parent):
        self.tokens = tokens          # np.int32 span
        self.blocks = blocks          # list[int], ceil(len(tokens)/bs)
        self.children = []            # children start block-aligned
        self.parent = parent
        self.touch = 0
        # recurrent-state snapshots this span owns: {tokens into the span
        # (block-aligned, > 0): snapshot entry}; None until one is taken
        self.snaps = None


class _PendingCopy:
    """One host-side COW order: copy pool block ``src`` into ``dst``
    before the adopter's prefill chunk. ``dead`` marks orders whose dst
    was freed (adopter cancelled/preempted) before the engine drained
    the plan — the copy must not run into a reallocated block."""

    __slots__ = ("src", "dst", "dead")

    def __init__(self, src, dst):
        self.src = src
        self.dst = dst
        self.dead = False


def _common_len(a, b):
    """Length of the common prefix of two int32 token arrays."""
    n = min(len(a), len(b))
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if len(neq) else n


class RadixPrefixBlockManager(RefBlockManager):
    """RefBlockManager + a token-level radix trie over the block pool
    (SGLang RadixAttention on vLLM-style paging).

    The trie matches the longest shared TOKEN span, not whole aligned
    blocks: edges own ref-counted physical blocks, a partially-filled
    boundary block is shared read-only and copied-on-write at first
    divergence (one fresh block; the engine applies the device copy via
    ``take_copy_plan`` before the adopter's prefill chunk), and
    ``commit_prefix`` inserts partial tails too — so divergence inside a
    block forfeits only the divergent suffix, not the whole tail.

    Blocks whose refcount drops to zero but that live in the trie are
    PARKED (still resident, counted as free); when the free list runs
    dry, eviction walks unreferenced trie leaves LRU-by-touch, one tail
    block at a time — so caching never reduces usable capacity.
    ``cache_epoch`` bumps on every eviction and commit; the scheduler's
    per-request match memo keys on it."""

    def __init__(self, num_blocks: int, block_size: int):
        super().__init__(num_blocks, block_size)
        self._root = _RadixNode(np.empty(0, np.int32), [], None)
        # one trie PER ADAPTER IDENTITY (ISSUE 14): KV computed under a
        # LoRA adapter is numerically that adapter's — tenants must never
        # adopt each other's blocks. The base-model (None) trie is the
        # legacy ``_root`` so adapter-free serving is untouched.
        self._roots: dict[object, _RadixNode] = {None: self._root}
        self._in_trie: dict[int, _RadixNode] = {}   # blk -> owning node
        self._parked: set[int] = set()              # trie blocks, rc == 0
        self._touch = 0
        self.cache_epoch = 0
        self._pending: list[_PendingCopy] = []
        self._copy_dst: dict[int, _PendingCopy] = {}
        self.cache_stats = {"hit_blocks": 0, "evictions": 0,
                            "lookup_blocks": 0, "token_hits": 0,
                            "partial_hits": 0, "lookup_tokens": 0}
        self.snap_capacity = 0       # > 0 after enable_snapshots

    # ---- recurrent-state snapshots (a model with linear layers): K/V
    # blocks of a matched prefix say nothing of the state at that depth,
    # so a hit is worth only as far as a snapshot exists. A fixed pool of
    # ``capacity`` entries (the device arrays are the executor's); an entry
    # is owned by a trie position (a node, tokens into it), leaves with
    # the blocks under it, and the least recently restored goes first
    # when the pool is full.
    def enable_snapshots(self, capacity: int):
        self.snap_capacity = int(capacity)
        self._lru_leaf = self._lru_leaf_keeping_snapshots
        self._snap_free = list(range(self.snap_capacity - 1, -1, -1))
        self._snap_home: dict[int, tuple] = {}   # entry -> (node, offset)
        self._snap_touch: dict[int, int] = {}
        self._snap_pending: set[int] = set()     # reserved, not yet owned
        # snap_offered_tokens: prompt tokens the K/V match alone offered
        # the admissions; beside ``token_hits`` (what was adopted: tokens
        # whose state came from a snapshot) the price of the rule of a hit
        self.cache_stats.update(snap_taken=0, snap_restored=0,
                                snap_evicted=0, snap_dropped=0,
                                snap_offered_tokens=0)
        self.ledger.set_snapshots(0, self.snap_capacity)

    def snapshots_held(self) -> int:
        """Entries a trie position owns or an admission has reserved."""
        return self.snap_capacity - len(self._snap_free) \
            if self.snap_capacity else 0

    def snapshot_audit(self) -> dict:
        """The pool as the manager holds it, for the ledger's identity and
        the quiescence check: entries ``owned`` by a trie position,
        ``reserved`` by an admission and ``free``, of ``capacity``, and
        the ``misplaced`` ones, whose trie position does not hold them."""
        if not self.snap_capacity:
            return {"owned": 0, "reserved": [], "free": 0, "capacity": 0,
                    "misplaced": []}
        return {"owned": len(self._snap_home),
                "reserved": sorted(self._snap_pending),
                "free": len(self._snap_free), "capacity": self.snap_capacity,
                "misplaced": [idx for idx, (node, off)
                              in self._snap_home.items()
                              if (node.snaps or {}).get(off) != idx
                              or off > len(node.tokens)]}

    def _snap_changed(self):
        self.cache_epoch += 1
        self.ledger.set_snapshots(self.snapshots_held(), self.snap_capacity)

    def _forget_snapshot(self, idx: int, stat: str):
        node, off = self._snap_home.pop(idx)
        del node.snaps[off]
        self._snap_touch.pop(idx, None)
        self._snap_free.append(idx)
        self.cache_stats[stat] += 1
        self._snap_changed()

    def _drop_snaps_past(self, node, n_tokens: int):
        """The snapshots of ``node`` deeper than ``n_tokens`` into it go
        with the blocks that held their prefix."""
        for off in [o for o in (node.snaps or ()) if o > n_tokens]:
            self._forget_snapshot(node.snaps[off], "snap_dropped")

    def state_hit(self, match: PrefixMatch) -> PrefixMatch:
        """THE RULE OF A HIT for a model with recurrent layers: the K/V
        match cut down to its deepest snapshot (block-aligned by
        construction, so no copy-on-write tail); nothing where the path
        has none."""
        depth = match.snapshot[0] if match.snapshot else 0
        return PrefixMatch(match.blocks[:depth // self.block_size], depth,
                           None, match.snapshot, match.token_count)

    def restored_snapshot(self, idx: int):
        """An admission restored entry ``idx``: it is the most recently
        used."""
        self._touch += 1
        self._snap_touch[idx] = self._touch
        self.cache_stats["snap_restored"] += 1

    def reserve_snapshot(self):
        """An entry for a snapshot about to be taken, or None (no pool):
        a free one, else the least recently restored one's, which leaves
        its trie position now. -> (entry, whether one was evicted)."""
        if not self.snap_capacity:
            return None
        evicted = False
        if not self._snap_free:
            if not self._snap_home:
                return None              # every entry reserved, none owned
            self._forget_snapshot(
                min(self._snap_home, key=lambda i: self._snap_touch[i]),
                "snap_evicted")
            evicted = True
        idx = self._snap_free.pop()
        self._snap_pending.add(idx)
        self._snap_changed()
        return idx, evicted

    def release_snapshot(self, idx: int):
        """A reserved entry whose snapshot was never taken (its request
        left before reaching the depth)."""
        self._snap_pending.discard(idx)
        self._snap_free.append(idx)
        self._snap_changed()

    def attach_snapshot(self, tokens, depth: int, idx: int, adapter=None):
        """Entry ``idx`` (reserved) now holds the state after
        ``tokens[:depth]``: hand it to the trie position at that depth.
        False (and the entry is free again) where the path no longer
        reaches that far or the position has a snapshot already."""
        toks = np.asarray(tokens, np.int32).reshape(-1)[:depth]
        node, d = self._root_for(adapter), 0
        while d < depth:
            best, bl = self._best_child(node, toks[d:])
            if best is None or bl == 0:
                break
            if d + bl == depth:
                off = depth - d
                if best.snaps is None:
                    best.snaps = {}
                if off in best.snaps:
                    break
                self._snap_pending.discard(idx)
                best.snaps[off] = idx
                self._snap_home[idx] = (best, off)
                self._touch += 1
                self._snap_touch[idx] = self._touch
                self.cache_stats["snap_taken"] += 1
                self._snap_changed()
                return True
            if bl < len(best.tokens):
                break
            node, d = best, d + bl
        self.cache_stats["snap_dropped"] += 1
        self.release_snapshot(idx)
        return False

    # ---- capacity: parked trie blocks are reclaimable, so count as free
    @property
    def free_blocks(self):
        return len(self._free) + len(self._parked)

    def _pop_free(self):
        if self._free:
            return self._free.pop()
        if self._parked:
            return self._evict_one()
        raise MemoryError("paged cache out of blocks")

    def _lru_leaf(self):
        """The least-recently touched childless leaf whose tail block is
        unreferenced, None if there is none."""
        victim = None
        stack = [ch for root in self._roots.values()
                 for ch in root.children]
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children)
            elif node.blocks and node.blocks[-1] in self._parked:
                if victim is None or node.touch < victim.touch:
                    victim = node
        return victim

    def _lru_leaf_keeping_snapshots(self):
        """``_lru_leaf`` for a manager with a snapshot pool (bound in its
        place by ``enable_snapshots``, so a manager without one runs the
        scan it always ran): a span that owns a state snapshot was seen
        twice at least, so the oldest of those (``kept``) goes only when no
        other span can."""
        victim = kept = None
        stack = [ch for root in self._roots.values()
                 for ch in root.children]
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children)
            elif node.blocks and node.blocks[-1] in self._parked:
                if node.snaps:
                    if kept is None or node.touch < kept.touch:
                        kept = node
                elif victim is None or node.touch < victim.touch:
                    victim = node
        return victim or kept

    def _evict_one(self) -> int:
        """Reclaim ONE parked block: the tail block of the least-recently
        touched childless leaf whose tail is unreferenced. Because
        adoption always takes the full matched path and release frees a
        table all at once, a parked block's whole suffix (deeper blocks
        of its node + every descendant) is parked too — so such a leaf
        always exists while ``_parked`` is non-empty."""
        victim = self._lru_leaf()
        if victim is None:       # unreachable by the suffix invariant
            raise MemoryError("paged cache out of blocks")
        from paddle_tpu.utils.faults import fault_point
        # chaos site: fires BEFORE any mutation, so an injected exception
        # leaves the trie, refcounts, and free list exactly as they were
        fault_point("serving.prefix_evict", manager=self,
                    blk=victim.blocks[-1], touch=victim.touch)
        blk = victim.blocks.pop()
        self._parked.discard(blk)
        self.ledger.unpark(blk)
        del self._in_trie[blk]
        victim.tokens = victim.tokens[:len(victim.blocks)
                                      * self.block_size]
        if victim.snaps:
            self._drop_snaps_past(victim, len(victim.tokens))
        if not victim.blocks and victim.parent is not None:
            victim.parent.children.remove(victim)
        self.cache_stats["evictions"] += 1
        self.cache_epoch += 1
        return blk

    def _release(self, blk):
        self._rc[blk] -= 1
        if self._rc[blk] == 0:
            del self._rc[blk]
            pend = self._copy_dst.pop(blk, None)
            if pend is not None:
                # the adopter died before its COW executed: cancel the
                # order and drop the pin on the source block
                pend.dead = True
                self.ledger.unpin(pend.src)
                self._release(pend.src)
            if blk in self._in_trie:
                self._parked.add(blk)
                self.ledger.park(blk)
            else:
                self._free.append(blk)

    def _retain(self, blk):
        self._parked.discard(blk)
        self.ledger.unpark(blk)
        super()._retain(blk)

    # --------------------------------------------------------- matching
    def _best_child(self, node, rem):
        """Child with the longest common token prefix with ``rem``.
        Siblings may overlap (first-writer-wins keeps physically distinct
        blocks for the same tokens), so this is argmax, not a dict hop."""
        best, bl = None, 0
        for ch in node.children:
            n = _common_len(ch.tokens, rem)
            if n > bl:
                best, bl = ch, n
        return best, bl

    def _root_for(self, adapter) -> _RadixNode:
        root = self._roots.get(adapter)
        if root is None:
            root = self._roots[adapter] = _RadixNode(
                np.empty(0, np.int32), [], None)
        return root

    def match_prefix(self, tokens, adapter=None) -> PrefixMatch:
        """Longest shared token span for this prompt, capped at len-1 so
        the last prompt token always prefills (its logits seed the first
        sample). Fully-matched aligned blocks are shared outright; the
        boundary block (divergence or span end mid-block) is offered as a
        copy-on-write partial hit. Matching walks ONLY the trie of the
        request's adapter identity — cross-tenant spans never match."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        cap = len(toks) - 1
        bs = self.block_size
        self.cache_stats["lookup_blocks"] += max(cap, 0) // bs
        self.cache_stats["lookup_tokens"] += max(cap, 0)
        self._touch += 1
        node, depth = self._root_for(adapter), 0
        blocks, cow, snap = [], None, None
        while depth < cap:
            best, bl = self._best_child(node, toks[depth:cap])
            if best is None or bl == 0:
                break
            best.touch = self._touch
            if best.snaps:
                off = max((o for o in best.snaps if o <= bl), default=0)
                if off:
                    snap = (depth + off, best.snaps[off])
            if bl == len(best.tokens) and bl % bs == 0:
                blocks.extend(best.blocks)
                depth += bl
                node = best
                continue
            # boundary inside ``best``: share its full sub-blocks, offer
            # the partial one copy-on-write
            n_full = bl // bs
            blocks.extend(best.blocks[:n_full])
            hit = bl % bs
            if hit:
                cow = (best.blocks[n_full], hit)
            depth += bl
            break
        return PrefixMatch(blocks, depth, cow, snap)

    # --------------------------------------------------------- adoption
    def adopt_prefix(self, seq_id, match) -> list:
        """Install a match as seq_id's table prefix: retain the shared
        blocks, and for a partial hit allocate one private block and
        queue the (src, dst) device copy. Exception-atomic: a failed
        allocation rolls every retain back."""
        assert seq_id not in self.tables
        blocks = list(match.blocks) if isinstance(match, PrefixMatch) \
            else list(match)
        cow = getattr(match, "cow", None)
        retained = []
        try:
            for blk in blocks:
                self._retain(blk)
                retained.append(blk)
            table = list(blocks)
            if cow is not None:
                src, hit = cow
                # pin src until the plan drains: a parked source must not
                # be evicted/reallocated before the copy program is issued
                self._retain(src)
                retained.append(src)
                dst = self._pop_free()
                self._rc[dst] = 1
                entry = _PendingCopy(src, dst)
                self._pending.append(entry)
                self._copy_dst[dst] = entry
                table.append(dst)
        except BaseException:
            for blk in reversed(retained):
                self._release(blk)
            raise
        self.tables[seq_id] = table
        # ledger transitions only on the success path: the rollback above
        # re-parks/frees via _release, whose own hooks keep it consistent
        for blk in table:
            self.ledger.table_enter(seq_id, blk)
        if cow is not None:
            self.ledger.pin(cow[0])
        self.cache_stats["hit_blocks"] += len(blocks)
        self.cache_stats["token_hits"] += getattr(
            match, "token_count", len(blocks) * self.block_size)
        if cow is not None:
            self.cache_stats["partial_hits"] += 1
        return table

    def take_copy_plan(self) -> list:
        """Drain the pending COW orders as (src, dst) pairs and drop the
        source pins. The engine applies them in ONE device copy before
        any other program of the tick writes the pool — jax data
        dependencies then order the copy before the adopters' prefill
        chunks and before any reallocation of a source block."""
        pairs = []
        pending, self._pending = self._pending, []
        for e in pending:
            if e.dead:
                continue
            pairs.append((e.src, e.dst))
            self._copy_dst.pop(e.dst, None)
            self.ledger.unpin(e.src)
            self._release(e.src)
        return pairs

    # ------------------------------------------------------- insertion
    def commit_prefix(self, seq_id, tokens, adapter=None):
        """Insert seq_id's token span — INCLUDING the partial tail block
        — so later requests can share it. Safe before the writes have
        executed on device (data dependencies order consumers after).
        Callers must pass only tokens whose KV is resident (the engine
        passes the cache frontier, not the just-sampled token). The span
        lands in the trie of ``adapter``'s identity only."""
        table = self.tables.get(seq_id, [])
        toks = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        n_tok = min(len(toks), len(table) * bs)
        for i, b in enumerate(table):     # window-recycled holes: stop
            if b is None:
                n_tok = min(n_tok, i * bs)
                break
        if n_tok <= 0:
            return
        self._insert(toks[:n_tok], table, self._root_for(adapter))
        self.cache_epoch += 1

    def _insert(self, toks, table, root=None):
        bs = self.block_size
        node, depth = (root if root is not None else self._root), 0
        while depth < len(toks):
            rem = toks[depth:]
            best, bl = self._best_child(node, rem)
            if best is None or bl == 0:
                self._attach(node, toks, depth, table)
                return
            if bl == len(best.tokens):
                if bl % bs == 0:
                    node = best
                    depth += bl
                    continue
                # fully matched a partial-tail leaf
                if len(rem) <= bl:
                    return                     # nothing new to insert
                own = table[(depth + bl) // bs]
                if (best.blocks[-1] == own
                        and best.blocks == table[depth // bs:
                                                 depth // bs
                                                 + len(best.blocks)]):
                    # same physical tail block: the original writer
                    # appended — extend the span in place
                    self._extend(best, toks, depth, table)
                else:
                    # same tokens, different block (a COW fork that grew
                    # past the shared span): overlapping sibling; match
                    # picks whichever overlaps a query longest
                    self._attach(node, toks, depth, table)
                return
            # divergence inside ``best``: split at the enclosing block
            # boundary, then attach the new branch (a committer that is
            # merely a PREFIX of ``best`` adds nothing — skip)
            sp = (bl // bs) * bs
            if 0 < sp < len(best.tokens):
                node = self._split(best, sp)
            if len(rem) > bl:
                self._attach(node, toks, depth + sp, table)
            return

    def _attach(self, parent, toks, depth, table):
        """New child of ``parent`` owning the committer's blocks from
        token ``depth`` on (block-aligned by construction)."""
        bs = self.block_size
        span = toks[depth:]
        start = depth // bs
        blocks = []
        for j in range(start, min(len(table),
                                  start + -(-len(span) // bs))):
            b = table[j]
            if b is None or b in self._in_trie:
                break                      # one trie home per block
            blocks.append(b)
        if not blocks:
            return
        span = span[:min(len(span), len(blocks) * bs)]
        self._touch += 1
        node = _RadixNode(span, blocks, parent)
        node.touch = self._touch
        parent.children.append(node)
        for b in blocks:
            self._in_trie[b] = node

    def _extend(self, node, toks, depth, table):
        """Grow a partial-tail node in place: same physical tail block,
        the committer wrote more tokens into it (and possibly beyond)."""
        bs = self.block_size
        span = toks[depth:]
        start = depth // bs
        blocks = list(node.blocks)
        for j in range(start + len(blocks),
                       min(len(table), start + -(-len(span) // bs))):
            b = table[j]
            if b is None or b in self._in_trie:
                break
            blocks.append(b)
        span = span[:min(len(span), len(blocks) * bs)]
        if len(span) <= len(node.tokens):
            return
        for b in blocks[len(node.blocks):]:
            self._in_trie[b] = node
        node.tokens = span
        node.blocks = blocks
        self._touch += 1
        node.touch = self._touch

    def _split(self, node, sp):
        """Split a node at block-aligned token offset ``sp``: the upper
        half keeps the shared prefix, the original node becomes its child
        with the remainder."""
        bs = self.block_size
        upper = _RadixNode(node.tokens[:sp], node.blocks[:sp // bs],
                           node.parent)
        upper.touch = node.touch
        parent = node.parent
        parent.children[parent.children.index(node)] = upper
        node.tokens = node.tokens[sp:]
        node.blocks = node.blocks[sp // bs:]
        node.parent = upper
        upper.children.append(node)
        for b in upper.blocks:
            self._in_trie[b] = upper
        if node.snaps:
            snaps, node.snaps, upper.snaps = node.snaps, {}, {}
            for off, idx in snaps.items():
                home = (upper, off) if off <= sp else (node, off - sp)
                home[0].snaps[home[1]] = idx
                self._snap_home[idx] = home
        return upper


class TwoSpaceBlockManager(RadixPrefixBlockManager):
    """The manager of the FULL space of a cache with two block spaces
    (``PagedKVCache.window_layers``), with the window space's beside it as
    ``window``: a plain :class:`BlockManager` over that space's own block
    numbers. A sequence's two tables are indexed alike (by ``position //
    block_size``) and grow together: ``allocate`` takes the same table
    positions in both spaces or in neither, ``free`` returns both. What a
    window layer no longer reads is freed in the window space alone
    (``window.free_prefix``, leaving ``None`` holes there), so a sequence
    holds O(window) blocks in it and O(length) here.

    Two managers and not one manager of two free lists: everything the
    engine, the scheduler and the ledger do to the full space (reservations,
    tables, the memory ledger) stays one manager's, exactly the one a model
    of one kind has, and the window space needs only what
    :class:`BlockManager` already is (holes, resumed prefix scans, a ledger
    of its own). Prefix adoption and forks would have to pair blocks across
    the spaces; the engine refuses them for such a model."""

    def __init__(self, num_blocks: int, block_size: int, window_blocks: int):
        super().__init__(num_blocks, block_size)
        self.window = BlockManager(window_blocks, block_size)

    def allocate(self, seq_id: int, n_tokens: int):
        short = (self.blocks_needed(n_tokens)
                 - len(self.window.tables.get(seq_id, ()))
                 - self.window.free_blocks)
        if short > 0:
            raise MemoryError(
                f"paged cache out of window-space blocks: {short} short "
                f"(of {self.window.num_blocks})")
        table = super().allocate(seq_id, n_tokens)   # raises: nothing taken
        self.window.allocate(seq_id, n_tokens)
        return table

    def free(self, seq_id: int):
        super().free(seq_id)
        self.window.free(seq_id)


def _rope_rows(positions, head_dim, base, scaling=None, max_pos=None):
    """cos/sin for PER-ROW positions: [B] -> [B, 1, 1, D/2] (ragged decode:
    every sequence sits at a different position). Shares the scaling math
    with ops.attention; dynamic-NTK uses each ROW's traced current length
    (positions + 1), so every sequence scales by its own length."""
    base, pos_div = A.resolve_rope_scaling(
        base, head_dim, scaling, allow_dynamic=False,
        max_position_embeddings=max_pos,
        cur_len=(positions + 1 if (scaling or {}).get("type") == "dynamic"
                 else None))
    base = jnp.asarray(base, jnp.float32).reshape(-1, 1)     # [B|1, 1]
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                     jnp.float32)[None, :] / head_dim))
    f = (positions.astype(jnp.float32) / pos_div)[:, None] * inv
    return (jnp.cos(f)[:, None, None, :], jnp.sin(f)[:, None, None, :])


def _apply_rope_rows(x, cos, sin):
    """x: [B, 1, H, D]; cos/sin: [B, 1, 1, D/2] (rotate-half, NeoX)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _scatter_prefill(pool, vals, tables, lens, num_blocks, block_size):
    """Write [B, S, H, D] tokens into the pool at table positions; token
    (b, i) -> (tables[b, i // bs], i % bs), dropped where i >= lens[b]."""
    bsz, s = vals.shape[:2]
    i = jnp.arange(s)
    blk = jnp.take_along_axis(tables, (i[None, :] // block_size), axis=1)
    blk = jnp.where(i[None, :] < lens[:, None], blk, num_blocks)  # OOB=drop
    off = jnp.broadcast_to(i[None, :] % block_size, (bsz, s))
    return pool.at[blk, off].set(vals, mode="drop")


def _scatter_decode(pool, vals, tables, lens, active, num_blocks, block_size):
    """Write ONE token per sequence at position lens[b]; inactive rows
    write nowhere (their blocks may already be recycled)."""
    blk = jnp.take_along_axis(tables, (lens // block_size)[:, None],
                              axis=1)[:, 0]
    blk = jnp.where(active, blk, num_blocks)  # OOB -> dropped
    off = lens % block_size
    return pool.at[blk, off].set(vals[:, 0], mode="drop")


# --------------------------------------------- context parallelism (cp)
# Under LLMEngine(cp=N) the executor runs every forward below inside
# shard_map over the "cp" mesh axis: pool arrays are sharded on their
# block axis (member s owns GLOBAL block ids [s*per, (s+1)*per),
# per = num_blocks/cp) while block tables, lens and activations stay
# replicated with GLOBAL ids. The forwards translate tables to LOCAL
# coordinates at their use sites (scatters drop non-owned writes, the
# attention kernels' partials mode masks non-owned reads) and merge the
# per-shard online-softmax partials — so the host-side block managers,
# radix trie and ledger never learn about sharding. ``cp_axis=None``
# (the default everywhere) leaves every trace byte-identical to pre-cp
# builds.

def _cp_local_tables(tables, cp_axis, per):
    """GLOBAL block-table entries -> this cp member's LOCAL pool
    coordinates: ids in [s*per, (s+1)*per) become [0, per); everything
    else (other members' blocks and the global OOB sentinel) becomes the
    LOCAL sentinel ``per`` — scatter-dropped on write, ownership-masked
    on read."""
    if cp_axis is None:
        return tables
    s = jax.lax.axis_index(cp_axis)
    loc = tables - s * per
    return jnp.where((loc >= 0) & (loc < per), loc, per)


def _cp_merge_chunk(o, m, l, cp_axis, dtype):
    """Merge chunk-prefill partials across cp. ``PT_CP_IMPL`` (read at
    TRACE time — flip between engine constructions) picks the ring
    rotation (default) or the Ulysses all_to_all head-reshard; both are
    bit-identical across members (global-order fold / symmetric
    collectives)."""
    from paddle_tpu.distributed.ring_attention import (finalize_partials,
                                                       ring_merge_partials)
    impl = os.environ.get("PT_CP_IMPL", "ring").strip().lower()
    if impl == "ulysses":
        from paddle_tpu.distributed.ulysses import ulysses_merge_partials
        o, m, l = ulysses_merge_partials(o, m, l, cp_axis)
    else:
        o, m, l = ring_merge_partials(o, m, l, cp_axis)
    return finalize_partials(o, l, dtype)


def _backbone(model):
    """Decoder backbone holding embed_tokens/layers/norm. Llama-family
    models wrap it in ``.model``; the MoE families (Mixtral, Qwen2-MoE,
    MoEForCausalLM) hang the parts directly off the LM."""
    return getattr(model, "model", model)


def _model_logits(model, x):
    """LM head: ``model.logits`` where it exists (weight-only-quant aware),
    the plain ``lm_head`` matmul otherwise (MoE families)."""
    with jax.named_scope("lm_head"):
        fn = getattr(model, "logits", None)
        if callable(fn):
            return fn(x)
        return _wo(x, model.lm_head)


def _mlp_out(lyr, h):
    """Per-layer MLP adapter: Mixtral-style layers carry an ``.moe``
    MoELayer, Qwen2-MoE puts a sparse block (or a dense LlamaMLP) at
    ``.mlp``. MoE blocks return ``(y, aux_loss)`` — the aux loss is a
    training regulariser, dropped at inference."""
    blk = lyr.moe if hasattr(lyr, "moe") else lyr.mlp
    with jax.named_scope("mlp"):
        out = blk(h)
    return out[0] if isinstance(out, (tuple, list)) else out


def _residual(x, branch, lyr, norm_name):
    """``x + branch``, the branch through the layer's own norm of it where
    the layer has one (a sandwich layer's ``input_layernorm_2`` after
    attention, ``post_attention_layernorm_2`` after the MLP)."""
    norm = getattr(lyr, norm_name, None)
    return x + (branch if norm is None else norm(branch))


def _pre_norm(x, lyr, norm_name):
    """The layer's norm of a branch's input, where it has one (an Olmo
    block norms each branch's output alone)."""
    norm = getattr(lyr, norm_name, None)
    return x if norm is None else norm(x)


def _qk_norm(att, q, k):
    """q and k through the attention module's norms over the whole
    projection, before RoPE, where it has them."""
    if getattr(att, "q_norm", None) is None:
        return q, k
    return att.q_norm(q), att.k_norm(k)


def _rotated(att, rope, t):
    """q or k through the caller's rotation ``rope``, unless the layer
    carries no positional encoding (``use_rope`` False: a NoPE layer)."""
    return rope(t) if getattr(att, "use_rope", True) else t


def _gated(att, h, attn_out):
    """The heads' output times ``sigmoid(h W_g)``, elementwise, where the
    attention module has an output gate (``gate_proj``)."""
    gate = getattr(att, "gate_proj", None)
    if gate is None:
        return attn_out
    return attn_out * jax.nn.sigmoid(
        _wo(h, gate).astype(jnp.float32)).astype(attn_out.dtype)


def _embed(model, ids):
    """The tokens' embedding rows, times the backbone's input scale where
    it has one (``embed_scale``: muP's sqrt(hidden))."""
    bb = _backbone(model)
    x = jnp.take(bb.embed_tokens, ids, axis=0)
    scale = getattr(bb, "embed_scale", None)
    return x * jnp.asarray(scale, x.dtype) if scale else x


def _kind_scope(cache, window):
    """The named scope of one paged kernel call in a model with two block
    spaces, so that a device trace tells its window layers' calls
    (``attention.window``) from its full layers' (``attention.full``);
    nothing for a model of one kind."""
    if not cache.window_layers:
        return contextlib.nullcontext()
    return jax.named_scope("attention.window" if window is not None
                           else "attention.full")


def _mlp_residual(x, lyr):
    return _residual(
        x, _mlp_out(lyr, _pre_norm(x, lyr, "post_attention_layernorm")),
        lyr, "post_attention_layernorm_2")


def _mlp_counted(x, lyr, live):
    """A K/V layer's MLP branch -> (x, counts): ``_mlp_residual`` and None
    for a layer whose MLP counts nothing; for an expert layer that says
    what it routed (``counts_routed``: its ``mlp(h, live)`` gives ``(y,
    counts)``, ``live()`` [B, S] False a padding token routed nowhere: a
    thunk, so that a program whose layers count nothing traces nothing for
    it) the same add with its counts."""
    if not counts_routed(lyr):
        return _mlp_residual(x, lyr), None
    h = _pre_norm(x, lyr, "post_attention_layernorm")
    with jax.named_scope("mlp"):
        y, counts = lyr.mlp(h, live())
    return _residual(x, y, lyr, "post_attention_layernorm_2"), counts


def _linear_residual(x, lyr, state, lens, rows=None, fresh=None):
    """A linear layer's body, shared by the three paged forwards: the
    mixer from each row's state, its branch norm and add, the MLP.
    ``state`` is the layer's ``(S, conv)`` over all slots; ``rows`` [A]
    the slots the rows of ``x`` belong to (None: row i is slot i), a
    sentinel >= slots a dead row whose state goes nowhere; ``fresh`` [A]
    marks rows that start from the zero state (a prompt's first token:
    a slot's state is zeroed by the program that starts its prompt, not
    by the host), None: every row goes on from its slot's state. -> (x,
    the layer's state)."""
    s_all, c_all = state
    if rows is None:
        s_in, c_in = s_all, c_all
    else:
        at = jnp.minimum(rows, s_all.shape[0] - 1)
        s_in, c_in = s_all[at], c_all[at]
    if fresh is not None:
        s_in = jnp.where(fresh[:, None, None, None], 0.0, s_in)
        c_in = jnp.where(fresh[:, None, None], 0, c_in).astype(c_all.dtype)
    with jax.named_scope("attention"):
        y, s_out, c_out = lyr.linear_attn.mix(x, s_in, c_in, lens)
        x = _residual(x, y, lyr, "input_layernorm_2")
    if rows is None:
        state = (s_out, c_out)
    else:
        state = (s_all.at[rows].set(s_out, mode="drop"),
                 c_all.at[rows].set(c_out, mode="drop"))
    return _mlp_residual(x, lyr), state


def _latent_residual(x, lyr, pool, positions, live, scatter, attend):
    """A latent layer's body, shared by the three paged forwards: what
    ``models/kimi_k2.py`` writes to and reads from the layer's pool of
    latent rows. ``positions`` [B, S] of the rows of ``x``, ``live`` [B, S]
    which of them carry a token (a padding row is routed to no expert);
    ``scatter(pool, rows [B, S, W]) -> pool`` writes the new positions'
    rows where the caller's tables put them; ``attend(att, h, rope, pool)
    -> [B, S, hidden]`` is the caller's attention branch over the pool as
    it then stands, in the form its call site asks: a decode tick scores
    one query a row ABSORBED (``att.absorbed`` around the one-token
    kernel), a prefill call a chunk EXPANDED (``_latent_chunk_attend``).
    -> (x, pool, counts): ``counts`` what the layer's MLP says it routed
    (``KimiK2MoE``), None for a dense one."""
    h = _pre_norm(x, lyr, "input_layernorm")
    with jax.named_scope("attention"):
        att = lyr.self_attn
        rope = att.rope(positions)
        pool = scatter(pool, att.cache_rows(h, *rope))
        x = x + attend(att, h, rope, pool)
    h = _pre_norm(x, lyr, "post_attention_layernorm")
    with jax.named_scope("mlp"):
        out = lyr.mlp(h, live) if lyr.sparse else lyr.mlp(h)
    y, counts = out if isinstance(out, tuple) else (out, None)
    return x + y, pool, counts


def _latent_chunk_attend(tables, offsets, chunk_lens):
    """``_latent_residual``'s ``attend`` of a prefill call: the chunk's
    rows, at ``offsets`` and ``chunk_lens`` long, scored in the expanded
    form against the pool prefix (the kernel expands each block of latent
    rows to a head's K and V in VMEM: no K or V in HBM, no context
    gathered)."""
    return lambda att, h, rope, pool: att.expanded(
        h, *rope, lambda q_nope, q_rope, w_kvb: paged_latent_chunk_attention(
            q_nope, q_rope, w_kvb, pool, tables, offsets, chunk_lens,
            scale=att.scale))


def _rope_scaling(cfg):
    """The ``rope_scaling`` of the rotation the K/V layers share. A latent
    layer rotates its own rope dims itself (``self_attn.rope``: YaRN blends
    each pair, which no (base, divisor) says)."""
    if LATENT_LAYER in (layer_kinds(cfg) or ()):
        return None
    return getattr(cfg, "rope_scaling", None)


def _run_stack(model, cache, x, tables, layer, linear=None, latent=None,
               wtables=None):
    """The decoder stack over ``x``, shared by the three paged forwards:
    every layer once and then the final norm; for a looped model
    (``cache.passes`` > 1) that whole pass ``passes`` times under one
    ``lax.fori_loop``, each pass starting from the normed output of the
    one before and writing pool rows of its own (``_pass_tables``), so the
    program holds one body a layer however many passes there are.

    ``layer(x, ci, lyr, pools, tables) -> (x, pools, counts)`` is the
    caller's body of a layer that keeps K/V, ``ci`` its index among those,
    ``pools`` its (k_pool, v_pool, k_scale, v_scale), the scales None for a
    bf16 cache, ``tables`` those of its block space (``wtables`` for a
    layer of ``cache.window_layers``) and ``counts`` what its MLP says it
    routed (None: nothing). ``linear(x, lyr, state) -> (x, state)`` is its body of a
    layer that carries a recurrent state (``layer_kinds``), ``state`` that
    layer's entry of ``cache.states``. ``latent(x, ci, lyr, pool) -> (x,
    pool, counts)`` is its body of a layer that keeps latent rows, ``pool``
    that layer's one pool (``k_pools[ci]``). Returns (the normed x, the
    cache's fields as the stack left them, for ``dataclasses.replace``, and
    the layers' ``counts`` summed: what the call's expert layers say they
    routed, ``KimiK2MoE`` / ``TrinityMoE``; None where no layer counts)."""
    bb = _backbone(model)
    if cache.passes != cache_passes(model.cfg):
        raise ValueError(
            f"the cache holds {cache.passes} pass(es) a layer, the model "
            f"runs {cache_passes(model.cfg)}: build it with "
            "PagedKVCache.init_for(model.cfg, ...)")
    kinds = layer_kinds(model.cfg)
    if kinds is not None and (
            len(cache.states) != kinds.count(LINEAR_LAYER)
            or len(cache.k_pools) != len(kinds) - kinds.count(LINEAR_LAYER)):
        raise ValueError(
            f"the cache holds {len(cache.k_pools)} K/V pool(s) and "
            f"{len(cache.states)} recurrent state(s), the model's layers "
            f"are {kinds}: build it with PagedKVCache.init_for(model.cfg, "
            "...)")

    routed = []     # layers' counts (a model that counts runs one pass)
    if cache.passes > 1 and any(map(counts_routed, bb.layers)):
        raise NotImplementedError(
            "a looped model whose expert layers count what they route: the "
            "counts cannot leave the loop over the passes")

    def one_pass(x, pools, states, tables):
        k, v, ks, vs = (list(p) for p in pools)
        states = list(states)
        ci = si = 0
        for li, lyr in enumerate(bb.layers):
            if kinds is not None and kinds[li] == LINEAR_LAYER:
                x, states[si] = linear(x, lyr, states[si])
                si += 1
                continue
            if kinds is not None and kinds[li] == LATENT_LAYER:
                x, k[ci], counts = latent(x, ci, lyr, k[ci])
                if counts is not None:
                    routed.append(counts)
                ci += 1
                continue
            x, (k[ci], v[ci], ks[ci], vs[ci]), counts = layer(
                x, ci, lyr, (k[ci], v[ci], ks[ci], vs[ci]),
                wtables if ci in cache.window_layers else tables)
            if counts is not None:
                routed.append(counts)
            ci += 1
        return bb.norm(x), (k, v, ks, vs), tuple(states)

    # a bf16 cache has no scale pools: a None a layer stands in for them
    no_scales = [None] * len(cache.k_pools)
    pools = (cache.k_pools, cache.v_pools, list(cache.k_scales) or no_scales,
             list(cache.v_scales) or no_scales)
    if cache.passes == 1:
        x, pools, states = one_pass(x, pools, cache.states, tables)
    else:
        def body(u, carry):
            with jax.named_scope("ut_step"):
                return one_pass(*carry, _pass_tables(
                    tables, u, cache.num_blocks, cache.passes))
        x, pools, states = jax.lax.fori_loop(
            0, cache.passes, body,
            (x, tuple(list(p) for p in pools), cache.states))
    k, v, ks, vs = pools
    if not cache.k_scales:
        ks = vs = ()
    return (x, dict(k_pools=k, v_pools=v, k_scales=tuple(ks),
                    v_scales=tuple(vs), states=states),
            sum(routed[1:], routed[0]) if routed else None)


def _last_logits(model, cache, x, lens):
    """The head's logits at each row's last live position, [A, V]. A cache
    with two block spaces takes the row first and the head after (its
    model's vocabulary is 200k rows: the head over every position of a
    2,048-token chunk would cost more than the chunk's layers); every
    other model's programs compute what they always computed."""
    at = lambda: jnp.maximum(lens - 1, 0)[:, None, None].astype(  # noqa: E731
        jnp.int32)
    if cache.window_layers:
        return _model_logits(model,
                             jnp.take_along_axis(x, at(), axis=1))[:, 0]
    logits = _model_logits(model, x)
    return jnp.take_along_axis(logits, at(), axis=1)[:, 0]


def _note_routed(routed, counts):
    """Hand a call's routing counts to the caller that asked for them:
    ``routed`` is the list a staged program passed to its forward (None:
    nobody asked), ``counts`` what ``_run_stack`` summed (None: no layer
    counts)."""
    if routed is not None and counts is not None:
        routed.append(counts)


def counts_routed(lyr) -> bool:
    """Whether a layer's MLP is an expert block that hands out what it
    routed (``KimiK2MoE``, ``TrinityMoE``: the class says so)."""
    return getattr(getattr(lyr, "mlp", None), "counts_routed", False)


def is_moe_model(model) -> bool:
    """True when any decoder layer routes through an MoE block (drives
    the ``serving.moe_dispatch`` chaos site in LLMEngine)."""
    return any(hasattr(lyr, "moe") or getattr(lyr, "sparse", False)
               for lyr in getattr(_backbone(model), "layers", ()))


def _lora_delta(x, lora, kind, li):
    """Batched multi-LoRA correction for ONE projection of ONE layer
    (ISSUE 14): ``delta[b] = (x[b] @ A_{aidx[b]}) @ B_{aidx[b]}`` with
    the alpha/r scale pre-folded into the B stack and zero for
    null-adapter rows. ``lora`` is the engine-built pytree:

      qkv_a/qkv_b/o_a/o_b  [L, cap, ...]  stacked adapter tensors
      perm / inv           [B]  rows sorted by cache index (null last) /
                                the inverse permutation
      gs                   [cap] TOKEN count per cache index (row count
                                × per-row width, in sorted order)

    Flattens the sorted rows to [B*S, k] and runs TWO grouped GEMMs
    (``ops/pallas/grouped_matmul`` — Pallas on TPU, XLA segment fallback
    elsewhere) so a heterogeneous batch is ragged per-adapter segments
    through one kernel. Rows past ``sum(gs)`` (the null-adapter tail) are
    UNSPECIFIED per the kernel contract and are masked to zero here."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    a_stack = lora[kind + "_a"][li]          # [cap, k, r]
    b_stack = lora[kind + "_b"][li]          # [cap, r, n]
    bsz, s, kdim = x.shape
    xf = x.astype(jnp.float32)
    xp = xf[lora["perm"]].reshape(bsz * s, kdim)
    t = grouped_matmul(xp, a_stack, lora["gs"])
    d = grouped_matmul(t, b_stack, lora["gs"])
    d = jnp.where(jnp.arange(bsz * s)[:, None] < jnp.sum(lora["gs"]),
                  d, 0.0)
    return d.reshape(bsz, s, -1)[lora["inv"]].astype(x.dtype)


def llama_prefill_paged(model, input_ids, prompt_lens, cache: PagedKVCache,
                        slot_ids=None, table_rows=None, lora=None,
                        cp_axis=None, routed=None, window_rows=None):
    """Prefill padded ragged prompts [B, S]; returns (last_logits, cache).
    ``routed``, here and in the other two forwards: a list that is handed
    what the call's expert layers routed (int32 [2]: the pairs sent to
    experts held here, the held experts hit; ``models/kimi_k2.py``), where
    the model counts that.

    Attention runs the padded-varlen path (kv_lens) — the fused kernel on
    TPU; K/V of every valid position is scattered into the block pool.
    ``last_logits`` are taken at each row's LAST VALID position.

    MID-FLIGHT ADMISSION (the continuous-batching engine): with
    ``slot_ids`` [A] + ``table_rows`` [A, max_blocks], the A prompt rows
    are written into cache SLOTS ``slot_ids`` (their new block-table rows
    installed on device) while every other slot's pools/tables/lens stay
    untouched — so prefill of admitted requests interleaves with decode of
    in-flight ones. Padding rows use slot_id >= num_slots (scatter-drop)
    and prompt_len 0. ``window_rows`` [A, max_blocks], here and in the
    chunk forward: the rows' tables in the window space, for a cache that
    has one."""
    cfg = model.cfg
    if getattr(cfg, "fp8", False):
        raise NotImplementedError(
            "paged serving ignores the fp8 training path (its inline "
            "decoder forward runs bf16 matmuls); serve an fp8-trained "
            "model with fp8=False weights, or use weight-only quantization")
    b, s = input_ids.shape
    bs = cache.block_size
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    wtables = new_wtables = cache.window_tables
    if slot_ids is None:
        tables = cache.block_tables          # row i == slot i (legacy)
        new_lens = prompt_lens
        new_tables = cache.block_tables
    else:
        slot_ids = jnp.asarray(slot_ids, jnp.int32)
        tables = jnp.asarray(table_rows, jnp.int32)   # [A, max_blocks]
        new_tables = cache.block_tables.at[slot_ids].set(tables, mode="drop")
        new_lens = cache.lens.at[slot_ids].set(prompt_lens, mode="drop")
        if cache.window_layers:
            wtables = jnp.asarray(window_rows, jnp.int32)
            new_wtables = cache.window_tables.at[slot_ids].set(wtables,
                                                               mode="drop")
    # cp: tables stay GLOBAL on device; only the pool scatters see the
    # LOCAL view (non-owned writes drop). In-prompt attention is dense
    # over the local pre-quant k/v — replicated compute, no merge needed.
    rtables = _cp_local_tables(tables, cp_axis, cache.num_blocks)
    x = _embed(model, input_ids)
    d = head_dim(cfg)
    scaling = _rope_scaling(cfg)
    windows = kv_windows(cfg)
    live = lambda: jnp.arange(s)[None, :] < prompt_lens[:, None]  # noqa: E731
    cos, sin = A.rope_cos_sin(
        s, d, base=cfg.rope_theta, scaling=scaling,
        max_position_embeddings=getattr(cfg, "max_position_embeddings",
                                        None),
        # dynamic-NTK: each ragged row scales by ITS prompt length
        cur_len=(prompt_lens if (scaling or {}).get("type") == "dynamic"
                 else None),
        allow_dynamic=False)
    rope = lambda t: A.apply_rope(t, cos, sin)  # noqa: E731

    def layer(x, li, lyr, pools, rtables):
        h = _pre_norm(x, lyr, "input_layernorm")
        with jax.named_scope("attention"):
            att = lyr.self_attn
            qkv = _wo(h, att.qkv_proj)
            if lora is not None:
                qkv = qkv + _lora_delta(h, lora, "qkv", li)
            if getattr(att, "qkv_bias", None) is not None:
                qkv = qkv + att.qkv_bias
            nh, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q, k = _qk_norm(att, q, k)
            q = _rotated(att, rope, q.reshape(b, s, nh, hd))
            k = _rotated(att, rope, k.reshape(b, s, nkv, hd))
            v = v.reshape(b, s, nkv, hd)
            # the prompt's own attention is dense over the LOCAL pre-
            # quantization k/v — only the pool writes quantize, so prefill
            # quality is exactly the decode dequantization error, never worse
            out = A.scaled_dot_product_attention(
                q, k, v, is_causal=True, kv_lens=prompt_lens,
                window=windows[li])
            hp = pools[0].shape[2]
            pools = _scatter_kv(pools, _pool_heads(k, hp), _pool_heads(v, hp),
                                _scatter_prefill, rtables, prompt_lens,
                                pools[0].shape[0], bs)
            attn_out = _gated(att, h, out.reshape(b, s, nh * hd))
            proj = _wo(attn_out, att.o_proj)
            if lora is not None:
                proj = proj + _lora_delta(attn_out, lora, "o", li)
            x = _residual(x, proj, lyr, "input_layernorm_2")
        x, counts = _mlp_counted(x, lyr, live)
        return x, pools, counts

    def linear(x, lyr, state):
        # every row is a prompt's start: from the zero state into its slot
        return _linear_residual(x, lyr, state, prompt_lens, slot_ids,
                                jnp.ones((b,), bool))

    def latent(x, ci, lyr, pool):
        # a whole prompt is a chunk at offset 0 over the rows it has just
        # written: one attention path for every prefill
        return _latent_residual(
            x, lyr, pool, jnp.broadcast_to(jnp.arange(s), (b, s)), live(),
            lambda pool, vals: _scatter_prefill(pool, vals, rtables,
                                                prompt_lens,
                                                cache.pool_rows, bs),
            _latent_chunk_attend(rtables, jnp.zeros((b,), jnp.int32),
                                 prompt_lens))

    x, fields, counts = _run_stack(model, cache, x, rtables, layer, linear,
                                   latent, wtables)
    _note_routed(routed, counts)
    last = _last_logits(model, cache, x, prompt_lens)
    new_cache = replace(cache, block_tables=new_tables, lens=new_lens,
                        window_tables=new_wtables, **fields)
    return last, new_cache


def llama_decode_step_paged(model, tokens, cache: PagedKVCache, active,
                            lora=None, cp_axis=None, routed=None):
    """One decode token per sequence. tokens: [B] int32; active: [B] bool
    (finished rows neither write KV nor advance). Returns (logits, cache)."""
    cfg = model.cfg
    b = tokens.shape[0]
    nb, bs = cache.num_blocks, cache.block_size
    x = _embed(model, tokens[:, None])                        # [B, 1, E]
    cos, sin = _rope_rows(cache.lens, head_dim(cfg), cfg.rope_theta,
                          _rope_scaling(cfg),
                          getattr(cfg, "max_position_embeddings", None))
    rope = lambda t: _apply_rope_rows(t, cos, sin)  # noqa: E731
    windows = kv_windows(cfg)
    new_lens = jnp.where(active, cache.lens + 1, cache.lens)
    rtables = _cp_local_tables(cache.block_tables, cp_axis, nb)

    def layer(x, li, lyr, pools, rtables):
        h = _pre_norm(x, lyr, "input_layernorm")
        with jax.named_scope("attention"):
            att = lyr.self_attn
            qkv = _wo(h, att.qkv_proj)
            if lora is not None:
                qkv = qkv + _lora_delta(h, lora, "qkv", li)
            if getattr(att, "qkv_bias", None) is not None:
                qkv = qkv + att.qkv_bias
            nh, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q, k = _qk_norm(att, q, k)
            q = _rotated(att, rope, q.reshape(b, 1, nh, hd))
            k = _rotated(att, rope, k.reshape(b, 1, nkv, hd))
            v = v.reshape(b, 1, nkv, hd)
            hp = pools[0].shape[2]
            q = _pool_heads(q, hp * (nh // nkv))
            pools = k_pool, v_pool, ks, vs = _scatter_kv(
                pools, _pool_heads(k, hp), _pool_heads(v, hp),
                _scatter_decode, rtables, cache.lens, active,
                pools[0].shape[0], bs)
            # a window layer attends the row's last ``window`` positions
            # alone, as its prefill did; the host frees the blocks below
            # them (``LLMEngine._recycle_window``), so a stale table entry
            # there may name a block another row holds by now: the kernel
            # never reads one
            window = windows[li]
            if cp_axis is None:
                with _kind_scope(cache, window):
                    out = paged_decode_attention(q[:, 0], k_pool, v_pool,
                                                 rtables, new_lens,
                                                 window=window, k_scale=ks,
                                                 v_scale=vs)
            else:
                # per-shard partials over the locally-owned blocks + ONE
                # psum-style merge: O(heads*dim) cross-shard bytes per step,
                # bit-identical on every member (replicated sampling)
                from paddle_tpu.distributed.ring_attention import (
                    finalize_partials, psum_merge_partials)
                o_p, m_p, l_p = paged_decode_attention(
                    q[:, 0], k_pool, v_pool, rtables, new_lens,
                    window=window, k_scale=ks, v_scale=vs, partials=True)
                o_p, m_p, l_p = psum_merge_partials(o_p, m_p, l_p, cp_axis)
                out = finalize_partials(o_p, l_p, q.dtype)
            attn_out = _gated(att, h,
                              out[..., :nh, :].reshape(b, 1, nh * hd))
            proj = _wo(attn_out, att.o_proj)
            if lora is not None:
                proj = proj + _lora_delta(attn_out, lora, "o", li)
            x = _residual(x, proj, lyr, "input_layernorm_2")
        x, counts = _mlp_counted(x, lyr, lambda: active[:, None])
        return x, pools, counts

    def linear(x, lyr, state):
        # one token a slot, each from its slot's state, in place; a slot
        # that does not run (length 0) keeps its state
        return _linear_residual(x, lyr, state, active.astype(jnp.int32))

    def latent(x, ci, lyr, pool):
        # one query a row: the absorbed form, nothing expanded
        return _latent_residual(
            x, lyr, pool, cache.lens[:, None], active[:, None],
            lambda pool, vals: _scatter_decode(pool, vals, rtables,
                                               cache.lens, active,
                                               cache.pool_rows, bs),
            lambda att, h, rope, pool: att.absorbed(
                h, *rope, lambda q: paged_latent_decode_attention(
                    q[:, 0], pool, rtables, new_lens, v_width=att.rank,
                    scale=att.scale)[:, None]))

    x, fields, counts = _run_stack(model, cache, x, rtables, layer, linear,
                                   latent, cache.window_tables)
    _note_routed(routed, counts)
    logits = _model_logits(model, x)[:, 0]
    return logits, replace(cache, lens=new_lens, **fields)


def llama_decode_tick(model, tokens, cache: PagedKVCache, active,
                      upd_rows, upd_cols, upd_vals, rng, temps, top_ps,
                      top_k=None, want_logp=False, lora=None,
                      logit_bias=None, cp_axis=None, routed=None,
                      upd_wvals=None):
    """ONE fused serving tick: apply incremental block-table updates
    (``tables[upd_rows[i], upd_cols[i]] = upd_vals[i]``, sentinel rows
    dropped — no host-side table rebuild/re-upload; a cache with a window
    space grows that table at the same rows and columns by ``upd_wvals``:
    both are indexed by position), run the decode step,
    and sample the next token ON DEVICE. The only per-tick host traffic is
    the [B] sampled-token fetch the engine needs for streaming/EOS.

    ``temps``/``top_ps``: [B] traced per-slot sampling params (each
    request its own; 0 temperature = greedy for that row). The sampler
    is handed the temperatures of the rows that RUN (0 for a row that
    is not ``active``: its token is discarded below, and a freed slot
    keeps its last request's temperature on the host), so a tick whose
    running rows are all greedy takes ``_sample_rows``' argmax branch:
    no sort, softmax, cumulative sum or draw over the vocabulary.
    ``top_k`` is static/global. ``want_logp`` (static): also return the
    [B, vocab] log-probs for beam selection, LEFT ON DEVICE. When False
    (greedy-only ticks) logp is () so no [B, vocab] f32 buffer is ever
    materialised."""
    from paddle_tpu.models.decoding import _sample_rows
    tables = cache.block_tables.at[upd_rows, upd_cols].set(upd_vals,
                                                           mode="drop")
    cache = replace(cache, block_tables=tables)
    if cache.window_layers:
        cache = replace(cache, window_tables=cache.window_tables.at[
            upd_rows, upd_cols].set(upd_wvals, mode="drop"))
    logits, cache = llama_decode_step_paged(model, tokens, cache, active,
                                            lora, cp_axis=cp_axis,
                                            routed=routed)
    logp = (jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            if want_logp else ())
    with jax.named_scope("sampler"):
        nxt = _sample_rows(logits.astype(jnp.float32), rng,
                           jnp.where(active, temps, 0), top_ps, top_k,
                           logit_bias)
    nxt = jnp.where(active, nxt.astype(jnp.int32), tokens)
    return nxt, logp, cache


def llama_decode_tick_async(model, tokens, cache: PagedKVCache, active,
                            stop, gen, max_gen, rng, temps, top_ps,
                            eos_id, top_k=None):
    """The pipelined twin of :func:`llama_decode_tick` (ISSUE 20): the
    token array it returns stays ON DEVICE and feeds the next call's
    ``tokens`` directly — the engine dispatches up to ``async_depth`` of
    these back-to-back and fetches results one tick late, hiding host
    emission under the in-flight device work.

    Because the host has not seen tick N's token when tick N+1
    dispatches, EOS/max-gen stop is evaluated IN THE JIT: ``stop`` is
    the accumulated device-side stop mask, and a row that sampled EOS
    (or hit ``max_gen``) at tick N is masked out of tick N+1's compute
    (``ran = active & ~stop``) before the host ever sees the token —
    over-dispatched ticks where every row is stopped run as all-masked
    no-ops the engine bills to nothing. ``eos_id`` is a traced int32
    (-1 when the engine has no EOS: token ids are non-negative, so the
    compare never fires).

    No table updates, grammar bias, LoRA, or beam logp: the engine
    drains the window and takes the synchronous tick for any tick that
    needs them, so this program stays a pure decode-cruise fast path.
    Returns (nxt, ran, stop', gen', cache) — ``ran`` is the mask of
    rows that actually computed this tick, which is exactly the rows
    the synchronous loop would have run, and the rows whose
    temperatures the sampler sees (0 for the others, as in the
    synchronous tick)."""
    from paddle_tpu.models.decoding import _sample_rows
    _note_trace("tick:async")
    ran = active & ~stop
    logits, cache = llama_decode_step_paged(model, tokens, cache, ran,
                                            None)
    with jax.named_scope("sampler"):
        nxt = _sample_rows(logits.astype(jnp.float32), rng,
                           jnp.where(ran, temps, 0), top_ps, top_k, None)
    nxt = jnp.where(ran, nxt.astype(jnp.int32), tokens)
    new_gen = gen + ran.astype(gen.dtype)
    stopped = ran & ((nxt == eos_id) | (new_gen >= max_gen))
    return nxt, ran, stop | stopped, new_gen, cache


# The forwards above are structure-agnostic via _backbone/_model_logits/
# _mlp_out, so they are ALSO the paged entry points for the MoE families
# (Mixtral, Qwen2-MoE): expert routing runs inside the same jitted
# prefill/decode, expert-parallel when traced under a mesh with ep > 1
# (MoELayer shards tokens over the data axes and all_to_alls expert
# slices via shard_map).
moe_prefill_paged = llama_prefill_paged
moe_decode_step_paged = llama_decode_step_paged
moe_decode_tick = llama_decode_tick


# ------------------------------------------- host staging, as one array
class Staging:
    """The small host arrays of one call of a forward program, handed over
    as ONE int32 vector: a numpy argument of a jitted call is a transfer of
    its own (~0.1 ms of the call on the chip, whatever its size, with the
    device idle behind a synchronous tick), so the executor packs a call's
    arrays and the program's first lines take the vector apart. The layout
    is written once, here: ``Staging(name=(dtype, shape), ...)`` in the
    order of :meth:`pack`'s arguments and :meth:`unpack`'s results, dtypes
    ``int32``, ``bool`` (an int32 0 / 1 in the vector) and ``float32`` (its
    bits). Hashed and compared by its fields: a static argument of the
    programs, so engines of one shape share a trace."""

    __slots__ = ("fields", "size", "_hash")
    DTYPES = ("int32", "bool", "float32")

    def __init__(self, **fields):
        out, lo = [], 0
        for name, (dtype, shape) in fields.items():
            if dtype not in self.DTYPES:
                raise TypeError(f"staging field {name!r}: dtype {dtype!r} "
                                f"is none of {self.DTYPES}")
            shape = tuple(int(d) for d in shape)
            hi = lo + int(np.prod(shape, dtype=np.int64))
            out.append((name, dtype, shape, lo, hi))
            lo = hi
        self.fields, self.size = tuple(out), lo
        self._hash = hash(self.fields)

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return isinstance(other, Staging) and self.fields == other.fields

    def pack(self, *arrays) -> np.ndarray:
        """-> a FRESH vector holding ``arrays``, each cast to its field's
        dtype: never a view of what it was handed, which a caller may
        change while the transfer is pending."""
        if len(arrays) != len(self.fields):
            raise TypeError(f"staging takes {len(self.fields)} arrays "
                            f"({', '.join(f[0] for f in self.fields)}), "
                            f"got {len(arrays)}")
        vec = np.empty(self.size, np.int32)
        for (name, dtype, shape, lo, hi), a in zip(self.fields, arrays):
            a = np.ascontiguousarray(a, dtype)
            if a.shape != shape:
                raise ValueError(f"staging field {name!r}: shape {a.shape}, "
                                 f"laid out as {shape}")
            vec[lo:hi] = (a.view(np.int32) if dtype == "float32"
                          else a).reshape(-1)
        return vec

    def unpack(self, vec) -> tuple:
        """Inside a traced program: the arrays :meth:`pack` was handed, in
        its order, dtypes and shapes (static slices of ``vec``)."""
        out = []
        for _, dtype, shape, lo, hi in self.fields:
            x = jax.lax.slice(vec, (lo,), (hi,)).reshape(shape)
            if dtype == "float32":
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            elif dtype == "bool":
                x = x != 0
            out.append(x)
        return tuple(out)


def tick_staging(num_slots: int, two_spaces: bool = False) -> Staging:
    """What :func:`tick_staged` is sent: ``llama_decode_tick``'s seven
    ``[num_slots]`` arrays, and for a cache with ``two_spaces`` the window
    table's new entries (``upd_wvals``) behind them."""
    i, f = ("int32", (num_slots,)), ("float32", (num_slots,))
    return Staging(tokens=i, active=("bool", (num_slots,)), upd_rows=i,
                   upd_cols=i, upd_vals=i, temps=f, top_ps=f,
                   **({"upd_wvals": i} if two_spaces else {}))


@functools.cache
def prefill_staging(rows: int, width: int, max_blocks: int,
                    chunked: bool, two_spaces: bool = False) -> Staging:
    """What a prefill program is sent for ``rows`` rows of ``width`` tokens:
    :func:`prefill_staged`'s four arrays, the rows' ``offsets`` for
    :func:`prefill_chunk_staged` (``chunked``), and for a cache with
    ``two_spaces`` the rows' window-space tables behind them."""
    r, t = ("int32", (rows,)), ("int32", (rows, max_blocks))
    return Staging(input_ids=("int32", (rows, width)), lens=r,
                   **({"offsets": r} if chunked else {}), slot_ids=r,
                   table_rows=t, **({"window_rows": t} if two_spaces else {}))


def prefill_staged(model, staged, cache: PagedKVCache, layout: Staging,
                   lora=None, cp_axis=None):
    """:func:`llama_prefill_paged` with its host arrays as one vector ->
    (last logits, cache, routed): ``routed`` the call's counts for a model
    with held experts (int32 [2]), None for any other."""
    ids, lens, slots, rows, *wrows = layout.unpack(staged)
    routed = []
    logits, cache = llama_prefill_paged(model, ids, lens, cache, slots, rows,
                                        lora, cp_axis, routed, *wrows)
    return logits, cache, (routed[0] if routed else None)


def tick_staged(model, staged, cache: PagedKVCache, rng, layout: Staging,
                top_k=None, want_logp=False, lora=None, logit_bias=None,
                cp_axis=None):
    """:func:`llama_decode_tick` with its host arrays as one vector. For a
    model with held experts the tick's two counts ride behind the slots'
    tokens (``nxt`` is ``[num_slots + 2]``): they come back in the fetch
    the tick makes anyway."""
    (tokens, active, rows, cols, vals, temps, top_ps,
     *wvals) = layout.unpack(staged)
    routed = []
    nxt, logp, cache = llama_decode_tick(
        model, tokens, cache, active, rows, cols, vals, rng, temps, top_ps,
        top_k, want_logp, lora, logit_bias, cp_axis, routed, *wvals)
    if routed:
        nxt = jnp.concatenate([nxt, routed[0].astype(nxt.dtype)])
    return nxt, logp, cache


# module-level jit wrappers: their compile caches persist across calls (a
# per-call jax.jit would recompile every request). The serving executor's
# three forwards take their host arrays staged; the generators below hand
# the array-signature bodies device arrays of their own.
_PREFILL_JIT = jax.jit(prefill_staged, static_argnums=(3,),
                       donate_argnums=(2,))
_GENERATE_PREFILL_JIT = jax.jit(llama_prefill_paged, donate_argnums=(3,))
_DECODE_JIT = jax.jit(llama_decode_step_paged)
_TICK_JIT = jax.jit(tick_staged, static_argnums=(4, 5, 6),
                    donate_argnums=(2,))
# The async tick donates the cache only on accelerator backends: PJRT's
# CPU client executes a computation inline on the dispatching thread
# when it must alias a donated input, which serializes the depth-K
# pipeline the tick exists to feed (dispatch would block for the full
# tick). On CPU the extra cache copy buys a dispatch that actually
# returns; on TPU dispatch is async regardless and donation keeps the
# KV pool single-buffered in HBM. The backend is asked at first use,
# never at import: importing this module must not claim a device.
@functools.cache
def _async_tick_jit():
    donate = () if jax.default_backend() == "cpu" else (2,)
    return jax.jit(llama_decode_tick_async, static_argnums=(11,),
                   donate_argnums=donate)


# jits registered by downstream serving modules (serving/transfer.py) so
# ONE clear_jit_caches() call covers every serving trace
_EXTRA_CLEAR: list = []


def clear_jit_caches():
    """Drop every module-level serving jit cache. Needed when trace-time
    context changes under the same call signature — flipping
    ``PT_GROUPED_GEMM``, patching a dispatcher's rule in a test, or
    entering/leaving a mesh re-routes layers, but the jit caches key on
    shapes only. The chunk kernels' own ``jit`` (one traced call for
    every layer of a program) goes with the programs that hold it."""
    if _async_tick_jit.cache_info().currsize:   # built: backend exists
        _async_tick_jit().clear_cache()
    for f in (_PREFILL_JIT, _GENERATE_PREFILL_JIT, _DECODE_JIT, _TICK_JIT,
              _PREFILL_CHUNK_JIT, _VERIFY_CHUNK_JIT, _REWIND_LENS_JIT,
              _PREFIX_COW_JIT, _STATE_TAKE_JIT, _STATE_RESTORE_JIT,
              _paged_chunk_call, _latent_chunk_call, *_EXTRA_CLEAR):
        f.clear_cache()
    from paddle_tpu.ops.pallas import gated_delta
    gated_delta.clear_caches()


def _copy_partial_blocks(pools, copy_src, copy_dst):
    """Copy-on-write pool block copies shared by every beam path.
    copy_src/copy_dst: [K] block ids, sentinel num_blocks = no copy."""
    return [p.at[copy_dst].set(p[jnp.clip(copy_src, 0, p.shape[0] - 1)],
                               mode="drop") for p in pools]


def _pass_rows(cache: PagedKVCache, ids):
    """[K] block ids -> the pool rows that hold them: the ids as they are
    for a one-pass cache, every pass's copy of each ([passes * K]) for a
    looped model's."""
    if cache.passes == 1:
        return ids
    return jnp.concatenate([
        _pass_tables(ids, u, cache.num_blocks, cache.passes)
        for u in range(cache.passes)])


def _cow_pools(cache: PagedKVCache, copy_src, copy_dst):
    """COW-copy the K/V pools AND (when quantized) their scale pools —
    a partial block's int8 codes are meaningless without the matching
    scale rows, so the two must fork together."""
    copy_src, copy_dst = _pass_rows(cache, copy_src), _pass_rows(cache,
                                                                 copy_dst)
    return (_copy_partial_blocks(cache.k_pools, copy_src, copy_dst),
            _copy_partial_blocks(cache.v_pools, copy_src, copy_dst),
            tuple(_copy_partial_blocks(cache.k_scales, copy_src, copy_dst)),
            tuple(_copy_partial_blocks(cache.v_scales, copy_src, copy_dst)))


def _beam_cache_update(cache: PagedKVCache, new_tables, copy_src, copy_dst):
    """Apply a beam reorder to the paged cache: install the forked block
    tables and copy the (at most one per beam) private partial blocks."""
    k, v, ks, vs = _cow_pools(cache, copy_src, copy_dst)
    return replace(cache, k_pools=k, v_pools=v, block_tables=new_tables,
                   k_scales=ks, v_scales=vs)


def _cp_copy_blocks(pools, copy_src, copy_dst, per, cp_axis):
    """Cross-shard block copy (cp COW): a copy's src and dst blocks may
    live on DIFFERENT cp members. Every member contributes its owned src
    rows (zeros elsewhere); since exactly one member owns each id, ONE
    psum replicates the K src blocks everywhere; the local-translated
    dst scatter then drops on all members but the dst owner. Sentinel
    pairs (src = dst = global num_blocks) contribute zero and drop."""
    s = jax.lax.axis_index(cp_axis)
    loc_src = copy_src - s * per
    own = (loc_src >= 0) & (loc_src < per)
    src_c = jnp.clip(loc_src, 0, per - 1)
    loc_dst = copy_dst - s * per
    loc_dst = jnp.where((loc_dst >= 0) & (loc_dst < per), loc_dst, per)
    out = []
    for p in pools:
        rows = jnp.where(own.reshape(own.shape + (1,) * (p.ndim - 1)),
                         p[src_c], 0)
        rows = jax.lax.psum(rows, cp_axis)
        out.append(p.at[loc_dst].set(rows.astype(p.dtype), mode="drop"))
    return out


def _prefix_cow_update(cache: PagedKVCache, copy_src, copy_dst,
                       cp_axis=None):
    """Radix prefix cache: copy adopted partial boundary blocks into the
    adopters' private blocks (copy-on-write at first divergence). Tables
    and lens are untouched — the adopters' tables already point at the
    dst blocks. copy_src/copy_dst: [K] block ids, sentinel num_blocks =
    no copy."""
    if cp_axis is not None:
        per = cache.num_blocks
        cp = lambda pools: _cp_copy_blocks(pools, copy_src, copy_dst,
                                           per, cp_axis)
        return replace(cache, k_pools=cp(cache.k_pools),
                       v_pools=cp(cache.v_pools),
                       k_scales=tuple(cp(cache.k_scales)),
                       v_scales=tuple(cp(cache.v_scales)))
    k, v, ks, vs = _cow_pools(cache, copy_src, copy_dst)
    return replace(cache, k_pools=k, v_pools=v, k_scales=ks, v_scales=vs)


_PREFIX_COW_JIT = jax.jit(_prefix_cow_update, donate_argnums=(0,))


def _state_take(snaps, states, slot, idx):
    """Snapshot pool entry ``idx`` <- slot ``slot``'s state, every linear
    layer's (``snaps`` is laid out as ``cache.states``, a row an entry)."""
    return tuple(tuple(pool.at[idx].set(live[slot])
                       for pool, live in zip(pair, state))
                 for pair, state in zip(snaps, states))


def _state_restore(cache: PagedKVCache, snaps, slot, idx):
    """Slot ``slot``'s state <- snapshot pool entry ``idx``."""
    return replace(cache, states=tuple(
        tuple(live.at[slot].set(pool[idx]) for pool, live in zip(pair, state))
        for pair, state in zip(snaps, cache.states)))


_STATE_TAKE_JIT = jax.jit(_state_take, donate_argnums=(0,))
_STATE_RESTORE_JIT = jax.jit(_state_restore, donate_argnums=(0,))


def _beam_select(running_lp, seqs, fin_seqs, fin_scores, logp, i,
                 prompt_len, eos_token_id, length_penalty):
    """b=1 adapter over decoding.beam_select — ONE shared implementation,
    so paged beam == static beam exactly by construction."""
    from paddle_tpu.models.decoding import beam_select
    out = beam_select(running_lp[None], seqs[None], fin_seqs[None],
                      fin_scores[None], logp[None], i, prompt_len,
                      eos_token_id, length_penalty)
    return tuple(x[0] for x in out)


def _beam_group_update(cache: PagedKVCache, slot_ids, rows, lens_val,
                       copy_src, copy_dst):
    """Engine-shaped beam reorder: install the K forked table rows at the
    group's cache slots, pin their lens, and copy the private partial
    blocks. slot_ids [K] int32; rows [K, max_blocks]; lens_val scalar;
    copy_src/copy_dst [K] (sentinel num_blocks = no copy)."""
    tables = cache.block_tables.at[slot_ids].set(rows)
    lens = cache.lens.at[slot_ids].set(jnp.int32(lens_val))
    k, v, ks, vs = _cow_pools(cache, copy_src, copy_dst)
    return replace(cache, k_pools=k, v_pools=v, block_tables=tables,
                   lens=lens, k_scales=ks, v_scales=vs)


def _beam_finalize(running_lp, seqs, fin_seqs, fin_scores, prompt_len,
                   max_new_tokens, eos_token_id, length_penalty):
    """Pick the best hypothesis among finished + still-running beams and
    EOS-fill past the first EOS — shared by ``paged_beam_search`` and the
    serving engine's beam groups. Returns (best_seq, best_score)."""
    run_score = running_lp / (float(max_new_tokens) ** length_penalty)
    all_scores = jnp.concatenate([fin_scores, run_score])
    all_seqs = jnp.concatenate([fin_seqs, seqs], axis=0)
    best = int(jnp.argmax(all_scores))
    best_seq = all_seqs[best]
    best_score = all_scores[best]
    if eos_token_id is not None:
        gen = best_seq[prompt_len:]
        seen = jnp.cumsum(gen == eos_token_id)
        after = jnp.concatenate([jnp.zeros((1,), bool), (seen > 0)[:-1]])
        best_seq = best_seq.at[prompt_len:].set(
            jnp.where(after, eos_token_id, gen))
    return best_seq, best_score


_BEAM_SELECT_JIT = jax.jit(_beam_select, static_argnums=(6, 7, 8))
_BEAM_UPDATE_JIT = jax.jit(_beam_cache_update, donate_argnums=(0,))
_BEAM_GROUP_UPDATE_JIT = jax.jit(_beam_group_update, donate_argnums=(0,))


def paged_beam_search(model, prompt, max_new_tokens=32, num_beams=4,
                      length_penalty=1.0, eos_token_id=None,
                      block_size=16, num_blocks=None):
    """Beam search IN THE PAGED PATH (single prompt, K beams as cache
    slots). Prompt blocks are SHARED across beams via refcounts
    (RefBlockManager); each reorder forks the parents' tables and copies
    only the private partial tail block — the append-only-pool
    copy-on-write. Selection math mirrors ``decoding.beam_search`` so the
    result equals the static-cache beam exactly.

    Returns (best_sequence [prompt+max_new], best_score).
    """
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    s = len(prompt)
    cfg = model.cfg
    if cache_passes(cfg) > 1:
        raise NotImplementedError(
            "paged_beam_search on a looped model: no test has forked the "
            "blocks of a cache that holds a row a pass")
    K = num_beams
    max_len = s + max_new_tokens
    max_blocks = -(-max_len // block_size)
    if num_blocks is None:
        num_blocks = K * max_blocks
    mgr = RefBlockManager(num_blocks, block_size)
    cache = PagedKVCache.init_for(cfg, num_blocks, block_size, K, max_blocks)

    # prefill once into beam 0's blocks, then fork the other beams
    sid = {j: j for j in range(K)}          # beam j -> mgr sequence id
    next_sid = K
    mgr.allocate(0, s)
    rows = np.full((K, max_blocks), num_blocks, np.int32)
    copy_src = np.full(K, num_blocks, np.int32)
    copy_dst = np.full(K, num_blocks, np.int32)
    for j in range(1, K):
        pair = mgr.fork(0, j, s)
        if pair is not None:
            copy_src[j], copy_dst[j] = pair
    for j in range(K):
        t = mgr.tables[j]
        rows[j, :len(t)] = t

    logits, cache = _GENERATE_PREFILL_JIT(
        model, jnp.asarray(prompt[None, :]), jnp.asarray([s], jnp.int32),
        cache, jnp.asarray([0], jnp.int32),
        jnp.asarray(rows[:1]))
    cache = replace(cache, block_tables=jnp.asarray(rows),
                    lens=jnp.full((K,), s, jnp.int32))
    cache = _BEAM_UPDATE_JIT(cache, jnp.asarray(rows),
                             jnp.asarray(copy_src), jnp.asarray(copy_dst))

    NEG = jnp.float32(-1e9)
    logp0 = jax.nn.log_softmax(logits[0].astype(jnp.float32))
    logp = jnp.broadcast_to(logp0[None], (K, cfg.vocab_size))
    running_lp = jnp.asarray([0.0] + [NEG] * (K - 1), jnp.float32)
    seqs = jnp.zeros((K, max_len), jnp.int32).at[:, :s].set(
        jnp.asarray(prompt)[None])
    fin_seqs = jnp.zeros_like(seqs)
    fin_scores = jnp.full((K,), NEG)

    for i in range(max_new_tokens):
        running_lp, seqs, fin_seqs, fin_scores, new_beam, new_tok = \
            _BEAM_SELECT_JIT(running_lp, seqs, fin_seqs, fin_scores, logp,
                             jnp.int32(i), s, eos_token_id,
                             float(length_penalty))
        if i == max_new_tokens - 1:
            break                      # pure selection, no forward after
        parents = np.asarray(new_beam)
        cur = s + i                    # tokens stored per beam so far
        # fork: new beam j adopts parent p's blocks; ensure room for the
        # write at position cur, privately per beam
        new_rows = np.full((K, max_blocks), num_blocks, np.int32)
        copy_src = np.full(K, num_blocks, np.int32)
        copy_dst = np.full(K, num_blocks, np.int32)
        new_sid_map = {}
        for j in range(K):
            dst = next_sid
            next_sid += 1
            pair = mgr.fork(sid[int(parents[j])], dst, cur)
            if pair is not None:
                copy_src[j], copy_dst[j] = pair
            new_sid_map[j] = dst
        for j in range(K):
            mgr.free(sid[j])
        sid = new_sid_map
        for j in range(K):
            t = mgr.allocate(sid[j], cur + 1)    # grow for this write
            new_rows[j, :len(t)] = t
        cache = _BEAM_UPDATE_JIT(cache, jnp.asarray(new_rows),
                                 jnp.asarray(copy_src),
                                 jnp.asarray(copy_dst))
        logits, cache = _DECODE_JIT(model, new_tok.astype(jnp.int32), cache,
                                    jnp.ones((K,), bool))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    return _beam_finalize(running_lp, seqs, fin_seqs, fin_scores, s,
                          max_new_tokens, eos_token_id, length_penalty)


def paged_generate(model, input_ids, prompt_lens, max_new_tokens=32,
                   block_size=16, num_blocks=None, eos_token_id=None,
                   temperature=0.0, top_k=None, top_p=None, rng=None):
    """Greedy continuous-batch decode over a paged cache.

    ``input_ids``: [B, S] right-padded ragged prompts with ``prompt_lens``
    [B]. The pool holds ``num_blocks`` blocks (default: exactly enough for
    Σ(prompt_len + max_new_tokens), the ragged bound — NOT B × max_len);
    finished sequences release their blocks back to the manager.

    Host-driven step loop (the serving-engine shape: scheduling/allocation
    on host, fixed-shape jitted compute on device). Returns [B, S +
    max_new_tokens] tokens (finished rows are tail-padded with
    ``eos_token_id``). ``temperature``/``top_k``/``top_p`` enable sampling
    (0.0 = greedy), sharing the sampler with models/decoding.py.
    """
    from paddle_tpu.models.decoding import _sample
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def pick(logits, key):
        return np.asarray(_sample(logits.astype(jnp.float32), key,
                                  temperature, top_k, top_p))
    cfg = model.cfg
    b, s = input_ids.shape
    lens_np = np.asarray(prompt_lens, np.int64)
    max_total = lens_np + max_new_tokens
    max_blocks = int(-(-(int(max_total.max())) // block_size))
    if num_blocks is None:
        num_blocks = int(sum(-(-int(t) // block_size) for t in max_total))
    mgr = BlockManager(num_blocks, block_size)
    for sid in range(b):
        mgr.allocate(sid, int(lens_np[sid]))
    cache = PagedKVCache.init_for(cfg, num_blocks, block_size, b, max_blocks)
    cache.block_tables = mgr.table_array(range(b), max_blocks)

    prefill = _GENERATE_PREFILL_JIT
    step = _DECODE_JIT

    logits, cache = prefill(model, jnp.asarray(input_ids),
                            jnp.asarray(lens_np, jnp.int32), cache)
    tokens = np.concatenate(
        [np.asarray(input_ids),
         np.zeros((b, max_new_tokens), np.asarray(input_ids).dtype)], axis=1)
    rng, sub = jax.random.split(rng)
    next_tok = pick(logits, sub)
    active = np.ones((b,), bool)
    cur = lens_np.copy()
    for sid in range(b):
        tokens[sid, cur[sid]] = next_tok[sid]
    if eos_token_id is not None:
        newly = next_tok == eos_token_id
        for sid in np.nonzero(newly)[0]:
            active[sid] = False
            mgr.free(int(sid))

    for _ in range(max_new_tokens - 1):
        if not active.any():
            break
        # grow tables for rows about to cross a block boundary
        for sid in range(b):
            if active[sid]:
                mgr.allocate(sid, int(cur[sid]) + 1)
        cache.block_tables = mgr.table_array(range(b), max_blocks)
        logits, cache = step(model, jnp.asarray(next_tok, jnp.int32), cache,
                             jnp.asarray(active))
        rng, sub = jax.random.split(rng)
        nxt = pick(logits, sub)
        next_tok = np.where(active, nxt, next_tok)
        cur = cur + active.astype(np.int64)
        for sid in range(b):
            if active[sid]:
                tokens[sid, cur[sid]] = next_tok[sid]
        if eos_token_id is not None:
            newly = active & (next_tok == eos_token_id)
            for sid in np.nonzero(newly)[0]:
                active[sid] = False
                mgr.free(int(sid))
    if eos_token_id is not None:
        # finished rows: pad the tail with EOS (HF/PaddleNLP convention)
        for sid in range(b):
            if not active[sid]:
                tokens[sid, int(cur[sid]) + 1:] = eos_token_id
    return jnp.asarray(tokens), cache


def llama_prefill_chunk_paged(model, input_ids, chunk_lens, offsets,
                              cache: PagedKVCache, slot_ids, table_rows,
                              full_logits=False, lora=None, cp_axis=None,
                              routed=None, window_rows=None):
    """CONTINUE a prefill: write chunk tokens at positions
    ``offsets[a] .. offsets[a]+chunk_lens[a]-1`` of their slots and attend
    each chunk query over the slot's WHOLE pool prefix (gather-based) —
    the vLLM-style chunked prefill that lets prompts longer than the
    prefill window stream in across engine ticks while other slots keep
    decoding. Returns (last_logits, cache); ``last_logits`` at each row's
    final chunk position (only meaningful on a request's last chunk).

    input_ids [A, C] (zero-padded), chunk_lens [A], offsets [A] (tokens
    already in the pool), slot_ids [A] (sentinel >= num_slots drops the
    row), table_rows [A, max_blocks] CURRENT tables covering
    offset+chunk. Dynamic-NTK rope is refused (chunk-end bases would
    desync across chunks).

    ``full_logits=True`` returns the whole [A, C, V] logit block instead
    of each row's last position — the speculative VERIFY forward: logit i
    of a row judges the proposal at position offset+i+1, so the engine
    needs every chunk position, not just the last."""
    cfg = model.cfg
    if (getattr(cfg, "rope_scaling", None) or {}).get("type") == "dynamic":
        raise NotImplementedError(
            "chunked prefill with dynamic-NTK rope is not supported "
            "(per-chunk bases would desync from the one-shot prefill)")
    if getattr(cfg, "fp8", False):
        raise NotImplementedError(
            "paged serving ignores the fp8 training path (see "
            "llama_prefill_paged); serve with fp8=False weights")
    a, c = input_ids.shape
    bs = cache.block_size
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    tables = jnp.asarray(table_rows, jnp.int32)
    new_tables = cache.block_tables.at[slot_ids].set(tables, mode="drop")
    new_lens = cache.lens.at[slot_ids].set(offsets + chunk_lens,
                                           mode="drop")
    wtables = new_wtables = cache.window_tables
    if cache.window_layers:
        wtables = jnp.asarray(window_rows, jnp.int32)
        new_wtables = cache.window_tables.at[slot_ids].set(wtables,
                                                           mode="drop")
    windows = kv_windows(cfg)
    # cp (ring-attention chunked prefill): quantize-on-write scatters land
    # each chunk's K/V in the owning shard via the LOCAL table view; the
    # pool read below computes per-shard partials over owned blocks only
    # and merges them across cp (ring rotation / Ulysses all_to_all)
    rtables = _cp_local_tables(tables, cp_axis, cache.num_blocks)

    x = _embed(model, input_ids)
    d = head_dim(cfg)
    live = lambda: jnp.arange(c)[None, :] < chunk_lens[:, None]  # noqa: E731
    positions = offsets[:, None] + jnp.arange(c, dtype=jnp.int32)  # [A, C]
    base, pos_div = A.resolve_rope_scaling(
        cfg.rope_theta, d, _rope_scaling(cfg), allow_dynamic=False,
        max_position_embeddings=getattr(cfg, "max_position_embeddings",
                                        None))
    inv = 1.0 / (jnp.asarray(base, jnp.float32)
                 ** (jnp.arange(0, d, 2, jnp.float32) / d))
    f = (positions.astype(jnp.float32) / pos_div)[:, :, None] * inv
    cos, sin = jnp.cos(f)[:, :, None, :], jnp.sin(f)[:, :, None, :]

    def rope(t):
        d2 = t.shape[-1] // 2
        t1, t2 = t[..., :d2], t[..., d2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1).astype(t.dtype)

    def layer(x, li, lyr, pools, rtables):
        h = _pre_norm(x, lyr, "input_layernorm")
        with jax.named_scope("attention"):
            att = lyr.self_attn
            qkv = _wo(h, att.qkv_proj)
            if lora is not None:
                qkv = qkv + _lora_delta(h, lora, "qkv", li)
            if getattr(att, "qkv_bias", None) is not None:
                qkv = qkv + att.qkv_bias
            nh, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q, k = _qk_norm(att, q, k)
            q = _rotated(att, rope, q.reshape(a, c, nh, hd))
            k = _rotated(att, rope, k.reshape(a, c, nkv, hd))
            v = v.reshape(a, c, nkv, hd)
            hp = pools[0].shape[2]
            q = _pool_heads(q, hp * (nh // nkv))
            # scatter the chunk FIRST so the gathered view holds prefix+chunk
            pools = k_pool, v_pool, ks, vs = _scatter_kv(
                pools, _pool_heads(k, hp), _pool_heads(v, hp),
                _scatter_decode_chunk, rtables, offsets, chunk_lens,
                pools[0].shape[0], bs)
            # ragged pool-direct attention: the kernel reads only each row's
            # live blocks (the XLA fallback reconstructs the old full
            # gather + dense-mask view, bit-compatible); of a window layer,
            # those from the first query's window on
            window = windows[li]
            if cp_axis is None:
                with _kind_scope(cache, window):
                    out = paged_chunk_attention(
                        q, k_pool, v_pool, rtables, offsets, chunk_lens,
                        window=window, k_scale=ks, v_scale=vs)
            else:
                o_p, m_p, l_p = paged_chunk_attention(
                    q, k_pool, v_pool, rtables, offsets, chunk_lens,
                    window=window, k_scale=ks, v_scale=vs, partials=True)
                out = _cp_merge_chunk(o_p, m_p, l_p, cp_axis, q.dtype)
            attn_out = _gated(att, h,
                              out[..., :nh, :].reshape(a, c, nh * hd))
            proj = _wo(attn_out, att.o_proj)
            if lora is not None:
                proj = proj + _lora_delta(attn_out, lora, "o", li)
            x = _residual(x, proj, lyr, "input_layernorm_2")
        x, counts = _mlp_counted(x, lyr, live)
        return x, pools, counts

    def linear(x, lyr, state):
        # a chunk goes on from the state its slot's earlier chunks (or a
        # restored snapshot) left; a chunk at offset 0 starts a prompt
        return _linear_residual(x, lyr, state, chunk_lens, slot_ids,
                                offsets == 0)

    def latent(x, ci, lyr, pool):
        return _latent_residual(
            x, lyr, pool, positions, live(),
            lambda pool, vals: _scatter_decode_chunk(
                pool, vals, rtables, offsets, chunk_lens, cache.pool_rows,
                bs),
            _latent_chunk_attend(rtables, offsets, chunk_lens))

    x, fields, counts = _run_stack(model, cache, x, rtables, layer, linear,
                                   latent, wtables)
    _note_routed(routed, counts)
    new_cache = replace(cache, block_tables=new_tables, lens=new_lens,
                        window_tables=new_wtables, **fields)
    if full_logits:
        return _model_logits(model, x), new_cache
    return _last_logits(model, cache, x, chunk_lens), new_cache


def _scatter_decode_chunk(pool, vals, tables, offsets, chunk_lens, nb, bs):
    """Scatter [A, C] chunk K/V at positions offset..offset+len-1 into the
    pool via each row's table; padding (i >= chunk_lens) scatters OOB."""
    a, c = vals.shape[:2]
    pos = offsets[:, None] + jnp.arange(c)[None, :]          # [A, C]
    blk_idx = pos // bs
    blk = jnp.take_along_axis(tables, jnp.minimum(blk_idx,
                                                  tables.shape[1] - 1),
                              axis=1)
    dest = blk * bs + pos % bs
    dest = jnp.where(jnp.arange(c)[None, :] < chunk_lens[:, None],
                     dest, nb * bs)                          # OOB drop
    flat = pool.reshape(nb * bs, *pool.shape[2:])
    flat = flat.at[dest.reshape(-1)].set(
        vals.reshape(a * c, *vals.shape[2:]), mode="drop")
    return flat.reshape(pool.shape)


def prefill_chunk_staged(model, staged, cache: PagedKVCache,
                         layout: Staging, lora=None, cp_axis=None):
    """:func:`llama_prefill_chunk_paged` with its host arrays as one
    vector -> (last logits, cache, routed), as :func:`prefill_staged`."""
    ids, lens, offs, slots, rows, *wrows = layout.unpack(staged)
    routed = []
    logits, cache = llama_prefill_chunk_paged(
        model, ids, lens, offs, cache, slots, rows, lora=lora,
        cp_axis=cp_axis, routed=routed,
        window_rows=wrows[0] if wrows else None)
    return logits, cache, (routed[0] if routed else None)


_PREFILL_CHUNK_JIT = jax.jit(prefill_chunk_staged, static_argnums=(3,),
                             donate_argnums=(2,))


# ------------------------------------------------ speculative helpers
# The multi-append/rewind primitives speculation needs, shared by the
# standalone generators (models/speculative.py) and the serving engine:
# the target VERIFY forward is the chunk prefill with full logits (multi-
# token append through the block tables), and the rollback past rejected
# positions is a pure LENGTH rewind — block tables untouched, because
# stale KV beyond a row's length pointer is masked by attention and
# positionally overwritten by the next append.

def llama_verify_chunk_paged(model, input_ids, chunk_lens, offsets,
                             cache: PagedKVCache, slot_ids, table_rows,
                             lora=None, cp_axis=None):
    """Speculative verify: one chunk forward returning [A, C, V] logits
    (see ``llama_prefill_chunk_paged`` — same append semantics, every
    chunk position's logits kept for accept/reject)."""
    return llama_prefill_chunk_paged(model, input_ids, chunk_lens, offsets,
                                     cache, slot_ids, table_rows,
                                     full_logits=True, lora=lora,
                                     cp_axis=cp_axis)


def spec_rewind_lens(cache: PagedKVCache, slot_ids, new_lens):
    """Roll the given slots' length pointers back past rejected
    speculative positions. Block tables are NOT touched: the blocks
    holding rejected KV stay owned by their sequences, their stale
    contents unreachable (attention masks ``pos >= lens``) until the next
    append overwrites them. slot_ids sentinel >= num_slots drops the
    row."""
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    lens = cache.lens.at[slot_ids].set(
        jnp.asarray(new_lens, jnp.int32), mode="drop")
    return replace(cache, lens=lens)


def spec_advance_frontiers(pos, draft_pos, n_new):
    """Commit one speculative round: the target frontier advances by the
    ``n_new`` committed tokens (accepted prefix + correction/bonus) and
    the draft frontier rolls back past everything it proposed beyond the
    new frontier — its stale cache entries get positionally overwritten
    by the next round's feed. Works on scalars or per-row arrays."""
    new_pos = pos + n_new
    return new_pos, np.minimum(draft_pos, new_pos)


def greedy_accept_length(verify_tokens, proposals):
    """Longest matching prefix between the target's argmax tokens and the
    draft's proposals — the greedy accept rule. ``verify_tokens`` may be
    longer than ``proposals`` (it usually carries the bonus position);
    works on [gamma] rows or [B, gamma] batches, returning a scalar or
    [B] counts."""
    v = np.asarray(verify_tokens)
    p = np.asarray(proposals)
    match = np.cumprod(v[..., : p.shape[-1]] == p, axis=-1)
    return match.sum(axis=-1)


def stochastic_accept_row(props, qs, ps, rng):
    """The Leviathan/Chen accept-reject rule over ONE row: accept
    proposal x_i with probability min(1, p_i(x_i)/q_i(x_i)); the first
    rejection resamples from the residual norm(max(0, p_i - q_i)); a
    fully accepted row draws the bonus token from p_gamma. ``ps`` holds
    len(props)+1 distributions (the extra one is the bonus position).
    Returns (committed tokens, n_accepted); the emitted stream is
    distributed exactly as sampling from ``ps`` alone, for ANY proposal
    distribution ``qs``."""
    new: list[int] = []
    n_acc = 0
    for i, x in enumerate(props):
        x = int(x)
        if rng.uniform() < min(1.0, float(ps[i][x])
                               / max(float(qs[i][x]), 1e-20)):
            new.append(x)
            n_acc += 1
        else:
            resid = np.maximum(ps[i] - qs[i], 0.0)
            z = resid.sum()
            resid = resid / z if z > 0 else ps[i]
            new.append(int(rng.choice(resid.size, p=resid)))
            break
    else:
        new.append(int(rng.choice(ps[len(props)].size, p=ps[len(props)])))
    return new, n_acc


_VERIFY_CHUNK_JIT = jax.jit(llama_verify_chunk_paged, donate_argnums=(4,))
_REWIND_LENS_JIT = jax.jit(spec_rewind_lens, donate_argnums=(0,))
