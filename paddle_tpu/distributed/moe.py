"""Mixture-of-Experts with expert parallelism (ref: ``python/paddle/
incubate/distributed/models/moe/`` — MoELayer, gate, dispatcher using
``c_alltoall`` over the expert-parallel NCCL group).

TPU-native design, two layers:

* **Sort-based routing** (``top_k_route``): tokens are argsort-grouped by
  expert id and capacity is enforced by position-within-group — the
  megablox-style O(T·k) formulation. No ``[T, E, C]`` one-hot
  dispatch/combine tensor is ever materialised (the GShard dense einsum
  form is O(T·E·C) memory and unusable at E=64, T=16k); dispatch is a
  scatter-add into ``[E·C, H]`` slots, combine a gather +
  scatter-add-by-token. Slot priority is (choice j, token t) — exactly the
  classic GShard queue order, so routing decisions (who is kept, who is
  dropped) are identical to the dense reference formulation
  (``top_k_gate`` below, kept as the executable spec).

* **Explicit expert-parallel dispatch** (``MoELayer`` under a mesh with an
  ``ep`` axis): a ``shard_map`` over ``ep`` where each shard routes its
  local tokens with LOCAL capacity (the reference's per-rank capacity
  semantics), builds an ``[E, C_local, H]`` send buffer, and a
  ``lax.all_to_all`` exchanges expert slices — the literal ``c_alltoall``
  the reference hand-codes, here riding ICI. Token results are invariant
  to slot order, so with no drops this equals the single-device layer
  exactly.

**Expert compute is a grouped GEMM** (``ops/pallas/grouped_matmul``): the
sorted route already lays tokens out contiguously per expert, so the MLP
runs directly over the ragged row partition — per-expert row offsets, no
``[E, C]`` slot padding in the FLOPs (MegaBlocks-style dropless; with
``capacity_factor=None`` nothing is ever dropped). On the EP path the
``[E, C_local, H]`` all_to_all wire format is kept, but each rank compacts
the received slots (occupancy counts ride a second tiny all_to_all) and
runs its local experts over ``sum(counts)`` rows instead of
``E_local·ep·C_local`` padded slots. ``PT_GROUPED_GEMM=0`` restores the
dense capacity-padded dispatch/compute path bit-for-bit (read at trace
time; re-trace after flipping).

**Router kinds and held experts** (``MoELayer(router=..., held=...)``).
``router="softmax"`` is every family above. ``router="sigmoid_bias"`` is
the DeepSeek-V3 / Kimi-K2 gate (``noaux_tc``): scores are sigmoids, the
top-k is chosen by score PLUS a per-expert selection bias, the combine
weights come from the UNBIASED scores, renormalised and times
``routed_scale``. ``held`` names the experts (global ids) whose weights
this layer holds, one chip's share of a wide expert-parallel deployment:
the layer routes over all ``num_experts``, computes the part of the routed
sum its own experts give and leaves the rest out (``held_forward``: the
shares of all chips add up to the uncut layer). No token is dropped
whatever the load; nothing stands in for the absent chips or their
exchange.

The gate also reports a **drop rate** (fraction of routing choices that
overflowed capacity) so saturation is observable (the reference exposes
drop behaviour through its gate counters).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.dtypes import get_default_dtype
from paddle_tpu.core.module import Module
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops.pallas.grouped_matmul import (
    grouped_gemm_enabled,
    grouped_matmul,
)


def _gate_probs(logits, k, renormalize=True):
    """softmax -> top-k -> (optionally) renormalised gates. Returns
    ([T,k] vals, idx, probs). ``renormalize=False`` keeps the raw softmax
    mass at the top-k (Qwen2-MoE's norm_topk_prob=False convention)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [T, k]
    if renormalize:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return gate_vals, gate_idx, probs


def _aux_parts(probs, gate_idx):
    """Switch load-balance loss ingredients: (mean prob/expert, frac top-1
    tokens/expert). aux = E * sum(me * ce); kept split so an ep shard_map
    can pmean the parts for the exact global loss."""
    e = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                  axis=0)
    return me, ce


def top_k_gate(logits, k: int, capacity: int, *, jitter_rng=None):
    """DENSE top-k gating with capacity — the executable GShard spec
    (ref gate/naive_gate.py). O(T·E·C) memory; kept as the reference
    semantics that ``top_k_route`` is tested against. Production paths use
    the sort-based route below.

    logits: [T, E]. Returns (dispatch [T, E, C] bool, combine [T, E, C]
    float, aux_loss scalar).
    """
    t, e = logits.shape
    gate_vals, gate_idx, probs = _gate_probs(logits, k)

    # GShard position computation: queue slot per token per choice
    dispatch = jnp.zeros((t, e, capacity), bool)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    offset = jnp.zeros((e,), jnp.int32)  # slots consumed by earlier choices
    for j in range(k):
        choice = jax.nn.one_hot(gate_idx[:, j], e, dtype=jnp.int32)  # [T,E]
        pos_in_e = jnp.cumsum(choice, axis=0) - 1 + offset[None, :]
        within = (pos_in_e < capacity) & (choice > 0)
        pos = jnp.sum(jnp.where(within, pos_in_e, 0), axis=1)  # [T]
        oh_pos = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        d_j = within[..., None] & (oh_pos[:, None, :] > 0.5)
        dispatch = dispatch | d_j
        combine = combine + d_j.astype(jnp.float32) * gate_vals[:, j][:, None, None]
        offset = offset + jnp.sum(choice, axis=0)

    me, ce = _aux_parts(probs, gate_idx)
    aux = e * jnp.sum(me * ce)
    return dispatch, combine, aux


def top_k_route(logits, k: int, capacity: int, renormalize: bool = True):
    """Sort-based top-k routing — O(T·k log) compute, O(T·k) memory.

    logits: [T, E]. Returns ``(route, aux, drop_rate)`` where ``route`` is a
    dict of [N = T·k] arrays in expert-sorted order:

      tok   int32  source token index
      expert int32 destination expert
      pos   int32  slot within the expert's queue (GShard (j, t) priority)
      keep  bool   pos < capacity (False = dropped)
      gate  f32    renormalised combine weight

    plus ``counts`` — the [E] per-expert assignment totals (pre-drop):
    exactly the segment sizes of the sorted layout, i.e. the
    ``group_sizes`` argument of the grouped GEMM.

    Identical keep/drop decisions to ``top_k_gate`` by construction: the
    flat assignment list is laid out choice-major (all j=0 entries before
    j=1) and the stable argsort preserves that order within each expert.
    """
    t, e = logits.shape
    n = t * k
    gate_vals, gate_idx, probs = _gate_probs(logits, k, renormalize)

    flat_e = gate_idx.T.reshape(n)                 # choice-major [k*T]
    flat_gate = gate_vals.T.reshape(n)
    flat_tok = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts           # exclusive prefix
    pos = jnp.arange(n, dtype=jnp.int32) - starts[se]
    keep = pos < capacity

    me, ce = _aux_parts(probs, gate_idx)
    # me/ce ride along so a distributed caller can pmean them for the
    # exact global aux loss without recomputing the gate
    route = dict(tok=flat_tok[order], expert=se, pos=pos, keep=keep,
                 gate=flat_gate[order], me=me, ce=ce, counts=counts)
    aux = e * jnp.sum(me * ce)
    drop_rate = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return route, aux, drop_rate


def sparse_dispatch(xt, route, num_experts: int, capacity: int):
    """Scatter tokens into expert slots: [T, H] -> [E, C, H]. Dropped
    assignments scatter out of bounds and are discarded (mode='drop')."""
    t, h = xt.shape
    dest = jnp.where(route["keep"],
                     route["expert"] * capacity + route["pos"],
                     num_experts * capacity)        # OOB sentinel
    x_e = jnp.zeros((num_experts * capacity, h), xt.dtype)
    x_e = x_e.at[dest].add(xt[route["tok"]], mode="drop")
    return x_e.reshape(num_experts, capacity, h), dest


def sparse_combine(y_e, route, dest, num_tokens: int):
    """Gather expert outputs back to tokens with gate weights:
    [E, C, H] -> [T, H]. Dropped assignments contribute zero."""
    e, c, h = y_e.shape
    y_flat = y_e.reshape(e * c, h)
    gathered = y_flat.at[dest].get(mode="fill", fill_value=0)
    gathered = gathered * route["gate"][:, None].astype(y_flat.dtype)
    yt = jnp.zeros((num_tokens, h), y_e.dtype)
    return yt.at[route["tok"]].add(gathered, mode="drop")


class ExpertMLP(Module):
    """E SwiGLU expert MLPs with a leading expert axis, ep-sharded."""

    def __init__(self, num_experts, hidden, intermediate, dtype=None):
        super().__init__()
        dtype = dtype or get_default_dtype()
        init = I.Normal(0.0, 0.02)
        self.gate_up = init((num_experts, hidden, 2 * intermediate), dtype)
        self.down = init((num_experts, intermediate, hidden), dtype)
        # experts over the dedicated ep mesh axis (expert parallelism)
        self.set_pspec("gate_up", P("ep", None, None))
        self.set_pspec("down", P("ep", None, None))

    def __call__(self, x_e):
        """x_e: [E, C, H] — per-expert token slots."""
        return expert_mlp_apply(x_e, *_expert_arrays(self, x_e.dtype))


def _expert_arrays(experts, dtype):
    """Weight-only-quantized expert stacks (``serving.quant.
    QuantizedExpertStack``) dequantize on the fly inside the jitted
    forward; plain arrays pass through untouched. Duck-typed on
    ``dequantize`` so this module never imports the serving layer."""
    gu, dn = experts.gate_up, experts.down
    if hasattr(gu, "dequantize"):
        gu = gu.dequantize(dtype)
    if hasattr(dn, "dequantize"):
        dn = dn.dequantize(dtype)
    return gu, dn


def expert_mlp_apply(x_e, gate_up, down):
    """Row-independent SwiGLU over expert slots (also used with LOCAL
    weight shards inside the ep shard_map)."""
    gu = jnp.einsum("ech,ehm->ecm", x_e, gate_up)
    gate, up = jnp.split(gu, 2, axis=-1)
    act = jax.nn.silu(gate) * up
    return jnp.einsum("ecm,emh->ech", act, down)


def grouped_mlp_apply(x_sorted, gate_up, down, group_sizes):
    """SwiGLU over the ragged sorted layout: ``x_sorted`` [N, H] rows
    contiguous per expert, ``group_sizes`` [E] segment sizes. Two grouped
    GEMMs — FLOPs track N, not E·capacity."""
    gu = grouped_matmul(x_sorted, gate_up, group_sizes)
    gate, up = jnp.split(gu, 2, axis=-1)
    act = jax.nn.silu(gate) * up
    return grouped_matmul(act, down, group_sizes)


def grouped_forward(xt, route, gate_up, down, num_tokens: int):
    """Sorted-layout expert forward + combine: gather tokens into
    expert-sorted rows (``route`` is already sorted), run the grouped
    SwiGLU over segment offsets, scatter-add back by source token with
    gate x keep weights. Dropped assignments ride through the GEMM with
    weight zero — identical results to the capacity path, without the
    ``[E, C, H]`` dispatch buffer."""
    x_sorted = xt[route["tok"]]
    y_sorted = grouped_mlp_apply(x_sorted, gate_up, down, route["counts"])
    wgt = (route["gate"] * route["keep"]).astype(y_sorted.dtype)
    yt = jnp.zeros((num_tokens, xt.shape[1]), y_sorted.dtype)
    return yt.at[route["tok"]].add(y_sorted * wgt[:, None], mode="drop")


ROUTERS = ("softmax", "sigmoid_bias")

# pairs (token, chosen expert) under which a held layer gathers every pair
# of a call: below it the rows of a call are few and one shape serves
_HELD_ROWS_FLOOR = 1024


def sigmoid_bias_gate(logits, bias, k, renormalize=True, scale=1.0):
    """The ``noaux_tc`` gate: ``s = sigmoid(logits)`` in float32, the k
    experts of largest ``s + bias``, their weights ``s`` (NOT ``s + bias``)
    over their sum (+1e-20) times ``scale`` -> ([T, k] weights, [T, k]
    expert ids). No groups: ``n_group`` 1, ``topk_group`` 1."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * scale, idx


def held_rows(pairs: int, held: int, experts: int) -> int:
    """Rows one pass of :func:`held_forward` gathers for a call of
    ``pairs`` (token, expert) pairs: all of them for a small call or a
    layer that holds every expert, else four times the share a uniform
    router sends to ``held`` of ``experts``, in whole 128-row tiles."""
    if pairs <= _HELD_ROWS_FLOOR or held >= experts:
        return pairs
    return min(pairs, max(_HELD_ROWS_FLOOR,
                          -(-4 * pairs * held // experts // 128) * 128))


def held_forward(xt, gate_vals, gate_idx, held, num_experts, gate_up, down,
                 live=None):
    """The routed sum over the experts this layer holds. xt [T, H];
    gate_vals / gate_idx [T, k] weights and GLOBAL expert ids over all
    ``num_experts``; ``held`` the global ids of the ``gate_up`` / ``down``
    stacks' rows (None: every expert, in order); ``live`` [T] bool, False
    a padding token that is routed nowhere (None: every token counts) ->
    (y [T, H] float32, routed_pairs, experts_hit): the pairs routed to a
    held expert and the held experts that got at least one.

    Pairs are sorted by held expert, those of absent experts last, and go
    through the grouped products ``rows`` at a time (:func:`held_rows`, a
    static bound): one pass in the common case, as many as the held pairs
    need under a ``lax.while_loop`` otherwise (none for a call that routes
    nothing here), so nothing is dropped whatever the load. (Not a
    ``lax.cond`` over two sizes: its second branch would hold every pair's
    rows at once, 0.9 GB of temporaries in a 2,048-token chunk call of
    Kimi-K2 whether or not it is taken.)"""
    t, k = gate_idx.shape
    n_held = gate_up.shape[0]
    if held is None:
        local = gate_idx
    else:
        lut = np.full((num_experts,), n_held, np.int32)
        lut[np.asarray(held)] = np.arange(n_held, dtype=np.int32)
        local = jnp.asarray(lut)[gate_idx]
    if live is not None:
        local = jnp.where(live[:, None], local, n_held)
    n = t * k
    flat_local = local.reshape(n)          # pair p: token p // k
    flat_gate = gate_vals.reshape(n)
    order = jnp.argsort(flat_local, stable=True)
    # a compare and a sum, not a scatter of every pair into a dozen bins
    counts = jnp.sum(flat_local[:, None] == jnp.arange(n_held)[None, :],
                     axis=0, dtype=jnp.int32)
    pairs = jnp.sum(counts)
    ends = jnp.cumsum(counts)
    rows = held_rows(n, n_held, num_experts)
    # whole passes: a pair index of ``n`` (token ``t``) stands for no pair
    order = jnp.pad(order, (0, -n % rows), constant_values=n)

    def one_pass(i, y):
        """Sorted pairs [i * rows, (i + 1) * rows): each expert's part of
        the window is its group."""
        lo = i * rows
        sel = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        tok = sel // k
        sizes = jnp.clip(jnp.minimum(ends, lo + rows)
                         - jnp.maximum(ends - counts, lo), 0)
        ys = grouped_mlp_apply(xt[tok], gate_up, down, sizes)
        # rows past the held pairs hold whatever the kernel left there
        ys = jnp.where((lo + jnp.arange(rows) < pairs)[:, None],
                       ys.astype(jnp.float32), 0.0) * flat_gate[sel][:, None]
        return y.at[tok].add(ys, mode="drop")

    y = jnp.zeros((t, xt.shape[1]), jnp.float32)
    if rows >= n:
        y = one_pass(0, y)
    else:
        _, y = jax.lax.while_loop(
            lambda c: c[0] * rows < pairs,
            lambda c: (c[0] + 1, one_pass(c[0], c[1])), (jnp.int32(0), y))
    return y, pairs, jnp.sum((counts > 0).astype(jnp.int32))


class MoELayer(Module):
    """Drop-in MLP replacement (ref MoELayer). Sort-based routing
    everywhere; under a mesh with ep > 1 the forward is a shard_map whose
    ``lax.all_to_all`` over the ep axis is the reference's ``c_alltoall``.
    The aux loss is returned for the trainer to add; the last drop rate is
    exposed via ``return_metrics=True``.

    ``router`` picks the gate (``ROUTERS``): ``"softmax"`` (softmax, top-k,
    optionally renormalised: Mixtral, Qwen2-MoE, ``moe_llm``) or
    ``"sigmoid_bias"`` (:func:`sigmoid_bias_gate`, with the selection bias
    ``gate_bias`` and ``routed_scale``). ``held``: the global ids of the
    experts whose weights this layer holds, in the order of the
    ``experts`` stack (``len(held)`` experts, not ``num_experts``); None
    holds them all. A layer with ``held`` or the sigmoid gate routes over
    all ``num_experts`` and computes its own experts' part
    (:func:`held_forward`), dropless, single-shard; its
    ``return_metrics`` also carry ``routed_pairs`` and ``experts_hit``."""

    def __init__(self, hidden, intermediate, num_experts, k=2,
                 capacity_factor=1.25, dtype=None, norm_topk_prob=True,
                 router="softmax", routed_scale=1.0, held=None):
        super().__init__()
        dtype = dtype or get_default_dtype()
        if router not in ROUTERS:
            raise ValueError(f"router {router!r} is none of {ROUTERS}")
        if held is not None:
            held = tuple(int(e) for e in held)
            if len(set(held)) != len(held) or not all(
                    0 <= e < num_experts for e in held):
                raise ValueError(f"held {held} are not distinct experts of "
                                 f"{num_experts}")
        self.gate_w = I.Normal(0.0, 0.02)((hidden, num_experts), jnp.float32)
        if router == "sigmoid_bias":
            self.gate_bias = jnp.zeros((num_experts,), jnp.float32)
        self.experts = ExpertMLP(num_experts if held is None else len(held),
                                 hidden, intermediate, dtype)
        self.num_experts, self.k, self.capacity_factor = num_experts, k, capacity_factor
        self.norm_topk_prob = norm_topk_prob
        self.router, self.routed_scale, self.held = router, routed_scale, held

    def _capacity(self, tokens: int) -> int:
        if self.capacity_factor is None:
            # EXACT (dropless) mode: every expert can take every token —
            # HF-style eval/inference semantics; memory O(T) per expert
            return max(tokens, 4)
        cap = int(self.capacity_factor * self.k * tokens / self.num_experts
                  + 0.999)
        return max(cap, 4)

    def __call__(self, x, return_aux=True, return_metrics=False, live=None):
        """``live`` [B, S] bool (a layer with held experts or the sigmoid
        gate only): False marks a padding token, which is routed to no
        expert and counted nowhere."""
        from paddle_tpu.distributed.mesh import current_mesh
        mesh = current_mesh()
        ep = mesh.size("ep") if mesh is not None else 1
        if self.router != "softmax" or self.held is not None:
            if ep > 1:
                raise NotImplementedError(
                    "a layer with held experts or the sigmoid gate is one "
                    "chip's share: it is not built for a mesh with ep > 1")
            y, pairs, hit = self._forward_held(x, live)
            if return_metrics:
                return y, 0.0, {"drop_rate": 0.0, "routed_pairs": pairs,
                                "experts_hit": hit}
            return (y, 0.0) if return_aux else y
        if ep > 1:
            y, aux, drop = self._forward_ep(x, mesh, ep)
        else:
            y, aux, drop = self._forward_local(x)
        if return_metrics:
            return y, aux, {"drop_rate": drop}
        return (y, aux) if return_aux else y

    # -- single-shard (or pure-GSPMD) path ----------------------------------
    def _forward_local(self, x):
        b, s, h = x.shape
        t = b * s
        e = self.num_experts
        cap = self._capacity(t)
        xt = x.reshape(t, h)
        logits = xt.astype(jnp.float32) @ self.gate_w
        route, aux, drop = top_k_route(logits, self.k, cap,
                                       self.norm_topk_prob)
        gate_up, down = _expert_arrays(self.experts, x.dtype)
        if grouped_gemm_enabled():
            yt = grouped_forward(xt, route, gate_up, down, t)
        else:
            x_e, dest = sparse_dispatch(xt, route, e, cap)
            y_e = expert_mlp_apply(x_e, gate_up, down)
            yt = sparse_combine(y_e, route, dest, t)
        return yt.reshape(b, s, h), aux, drop

    # -- one chip's share of the experts, or the sigmoid gate ---------------
    def _forward_held(self, x, live=None):
        b, s, h = x.shape
        xt = x.reshape(b * s, h)
        # the gate in float32 as published: on the TPU a float32 product
        # at the default precision is a bfloat16 one
        logits = jnp.dot(xt.astype(jnp.float32), self.gate_w,
                         precision=jax.lax.Precision.HIGHEST)
        if self.router == "sigmoid_bias":
            vals, idx = sigmoid_bias_gate(logits, self.gate_bias, self.k,
                                          self.norm_topk_prob,
                                          self.routed_scale)
        else:
            vals, idx, _ = _gate_probs(logits, self.k, self.norm_topk_prob)
            vals = vals * self.routed_scale
        gate_up, down = _expert_arrays(self.experts, x.dtype)
        y, pairs, hit = held_forward(
            xt, vals, idx, self.held, self.num_experts, gate_up, down,
            None if live is None else live.reshape(b * s))
        return y.astype(x.dtype).reshape(b, s, h), pairs, hit

    # -- expert-parallel path: shard_map + all_to_all over the ep axis ------
    def _forward_ep(self, x, mesh, ep):
        from jax import shard_map

        e = self.num_experts
        if e % ep != 0:
            raise ValueError(f"num_experts={e} not divisible by ep={ep}")
        b, s, h = x.shape
        # tokens are sharded over ALL data axes, not just ep — over the
        # FLATTENED token dim, so any (b, s) with b*s divisible by the
        # shard count works (serving's chunked prefill runs b=1). When b
        # itself divides, each shard gets the same whole sequences as the
        # old batch-dim sharding (row-major flatten), so results are
        # unchanged.
        data_shards = mesh.dp * mesh.fsdp * ep
        t = b * s
        if t % data_shards != 0:
            raise ValueError(
                f"tokens {t} (= {b}x{s}) not divisible by "
                f"dp*fsdp*ep={data_shards} "
                "(tokens are sharded over the data axes)")
        # LOCAL capacity — the reference's per-rank semantics: each rank may
        # fill at most C_local slots of each (global) expert
        cap = self._capacity(t // data_shards)
        k = self.k
        renorm = self.norm_topk_prob

        batch_axes = ("dp", "fsdp", "ep")
        xspec = P(batch_axes, None)

        def local(xt, gate_w, gate_up, down):
            tl, hl = xt.shape
            logits = xt.astype(jnp.float32) @ gate_w
            route, _, _ = top_k_route(logits, k, cap, renorm)
            # exact global aux loss: pmean the gate's ingredients
            me = jax.lax.pmean(route["me"], batch_axes)
            ce = jax.lax.pmean(route["ce"], batch_axes)
            aux = e * jnp.sum(me * ce)
            drop = 1.0 - jax.lax.pmean(
                jnp.mean(route["keep"].astype(jnp.float32)), batch_axes)

            # send buffer: my tokens in every expert's queue -> [E, C, H]
            x_send, dest = sparse_dispatch(xt, route, e, cap)
            # [E, C, H] -> [ep, E_loc, C, H]; a2a: recv[s] = shard s's slots
            # for MY experts (the c_alltoall)
            x_send = x_send.reshape(ep, e // ep, cap, hl)
            x_recv = jax.lax.all_to_all(x_send, "ep", split_axis=0,
                                        concat_axis=0)
            el = e // ep
            if grouped_gemm_enabled():
                # occupancy counts ride a second (tiny) all_to_all:
                # cnt_recv[s, el] = slots shard s filled for my expert el.
                # Kept assignments fill slots 0..kept-1 contiguously, so
                # the received ragged rows compact into per-expert
                # segments and the MLP runs over sum(counts) rows instead
                # of el*ep*cap padded slots.
                kept = route["keep"].astype(jnp.int32)
                cnt_send = jnp.zeros((e,), jnp.int32).at[route["expert"]] \
                    .add(kept).reshape(ep, el)
                cnt_recv = jax.lax.all_to_all(cnt_send, "ep", split_axis=0,
                                              concat_axis=0)
                flat = jnp.swapaxes(x_recv, 0, 1).reshape(el * ep * cap, hl)
                sizes = jnp.sum(cnt_recv, axis=0)             # [el]
                seg_start = jnp.cumsum(sizes) - sizes
                # rank of slot (el, s, c) within its expert's segment:
                # senders before s, then c within sender s
                before = (jnp.cumsum(cnt_recv, 0) - cnt_recv).T  # [el, ep]
                c_idx = jnp.arange(cap)[None, None, :]
                valid = c_idx < cnt_recv.T[:, :, None]
                destc = jnp.where(
                    valid,
                    (seg_start[:, None] + before)[:, :, None] + c_idx,
                    el * ep * cap).reshape(-1)
                xc = jnp.zeros((el * ep * cap, hl), xt.dtype) \
                    .at[destc].set(flat, mode="drop")
                yc = grouped_mlp_apply(xc, gate_up, down, sizes)
                y_flat = yc.at[destc].get(mode="fill", fill_value=0)
                y_loc = y_flat.reshape(el, ep, cap, hl)
            else:
                # dense path: fold senders into the slot dim, padded MLP
                x_loc = jnp.swapaxes(x_recv, 0, 1).reshape(el, ep * cap, hl)
                y_loc = expert_mlp_apply(x_loc, gate_up, down) \
                    .reshape(el, ep, cap, hl)
            # reverse exchange back to the senders
            y_back = jnp.swapaxes(y_loc, 0, 1)
            y_recv = jax.lax.all_to_all(y_back, "ep", split_axis=0,
                                        concat_axis=0)
            y_e = y_recv.reshape(e, cap, hl)
            yt = sparse_combine(y_e, route, dest, tl)
            return yt, aux, drop

        # check_vma off: the grouped-GEMM Pallas call in the body carries
        # no varying-axes annotation, which the checker refuses on a TPU
        fn = shard_map(
            local, mesh=mesh.mesh,
            in_specs=(xspec, P(), P("ep", None, None), P("ep", None, None)),
            out_specs=(xspec, P(), P()), check_vma=False)
        # quantized stacks dequantize BEFORE the shard_map (codes would
        # need their own ep pspecs); the all_to_all wire format and the
        # per-shard compute are unchanged
        gate_up, down = _expert_arrays(self.experts, x.dtype)
        yt, aux, drop = fn(x.reshape(t, h), self.gate_w, gate_up, down)
        return yt.reshape(b, s, h), aux, drop
