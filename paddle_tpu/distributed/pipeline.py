"""Pipeline parallelism (ref: ``python/paddle/distributed/fleet/
meta_parallel/pipeline_parallel.py`` — PipelineLayer + 1F1B scheduler).

The reference runs an imperative per-rank scheduler exchanging activations
with NCCL send/recv. TPU-native formulation: SPMD over the ``pp`` mesh axis —
stage weights live stacked on a leading pp dimension sharded P("pp", ...),
the schedule is a ``lax.scan`` over global ticks, and the stage handoff is a
``ppermute`` ring.

Two schedules live here:

- ``pipeline_apply`` — forward-only fill-drain (GPipe) wavefront. Used for
  inference/eval and by ``PipelineLayer.__call__``; differentiating through
  it gives GPipe's all-forward-then-all-backward with per-stage remat.
- ``pipeline_train_1f1b`` — TRUE 1F1B training schedule with a manually
  written backward pass (the reference's ``_1f1b_schedule``): each global
  tick every stage runs one forward microbatch AND one backward microbatch
  (the SPMD "shifted-buffer" formulation of 1F1B — GSPMD-style), so the
  in-flight residual window is a ring of ``2*pp - 1`` saved stage inputs
  **independent of the number of microbatches M** (GPipe stores M). The
  backward slot recomputes the stage forward from its saved input
  (activation-checkpoint style, like the reference's recompute+1F1B mode)
  and accumulates param grads in fp32. Steady-state bubble fraction is
  ``2(pp-1)/(M + 2(pp-1))`` and vanishes as M grows.

Interleaved virtual stages (Megatron's V>1 chunks per device) are
DELIBERATELY not implemented: in this SPMD lockstep-tick formulation every
device executes every tick's full chunk workload with masking, so
interleaving INCREASES total tick cost — the fill/drain ticks still cost a
full V-chunk step while covering 1/V the work, making the bubble
``2(V*pp-1)`` chunk-slots ≈ strictly worse than the non-interleaved
``2(pp-1)`` full-slots. The interleave only pays off with per-device
dynamic schedules (real divergent control flow between collectives), which
SPMD-with-collectives cannot express safely. Megatron wins that trade
because its per-rank imperative scheduler skips idle slots entirely.

A refinement of that cost model motivates the THIRD schedule here,
``pipeline_train_1f1b(zero_bubble=True)`` — reachable as
``pipeline_train_step(..., schedule="zb1")`` (zero-bubble, ZB-H1 style —
ref: Fleet's
interleaved/zero-bubble pipeline work; paper "Zero Bubble Pipeline
Parallelism"): the scan ticks are NOT a global barrier — the only sync is
the pairwise ``ppermute``, so device ``s`` at tick ``t+1`` waits only for
its neighbours' tick-``t`` sends, and per-device ``lax.cond`` slack flows
through the dependency DAG. The step's wall-clock is the DAG's longest
path: fill chain ``(pp-1)·F``, steady ``M·(F+B_dx+W)``, drain chain
``(pp-1)·B`` — and the drain hop cost is the part a schedule CAN shrink.
1F1B pays the FULL backward (recompute+dx+dw) on every drain hop; ZB-H1
splits it: drain hops compute dx ONLY (the cotangent moves on at
``B_dx ≈ recompute+dx`` cost) while the deferred weight-grads run in tail
ticks OFF the critical path. Saving: ``(pp-1)·W`` per step, ~10-15% of
the 1F1B bubble-dominated regime at small M. Interleaved-VPP remains
rejected: its fill chain still traverses all ``V·pp`` chunks at ``F/V``
each (no path shortening in the SPMD DAG), whereas the W-split shortens a
real chain segment.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax.lax import axis_size

from paddle_tpu.core.module import Module


def stack_layers(layers: list[Module]) -> Module:
    """Stack N structurally-identical layer pytrees on a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *layers)


def unstack_layers(stacked: Module, n: int) -> list[Module]:
    return [jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(n)]


def pipeline_apply(stacked_stage_params, layer_fn: Callable, x_microbatches,
                   *, axis_name: str = "pp", layers_per_stage: int = 1,
                   remat: bool = True):
    """Run microbatches through the pp-stage ring. Call inside shard_map.

    stacked_stage_params: this stage's layers stacked [layers_per_stage, ...]
      (globally [pp * layers_per_stage, ...] sharded on the leading axis).
    layer_fn(layer_params, x) -> x: applies ONE layer.
    x_microbatches: [M, mb, ...] — every stage receives the same microbatch
      stream; non-first stages ignore it (they consume the ring instead).
    Returns [M, mb, ...]: last stage's outputs (valid on the last stage;
      other stages hold garbage — psum/broadcast outside if needed).
    """
    n_stages = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m_total = x_microbatches.shape[0]
    ticks = m_total + n_stages - 1

    def apply_stage(params, x):
        def body(h, lyr):
            return layer_fn(lyr, h), None
        if remat:
            run = jax.checkpoint(lambda p, v: lax.scan(body, v, p)[0])
        else:
            run = lambda p, v: lax.scan(body, v, p)[0]
        return run(params, x)

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    mb_shape = x_microbatches.shape[1:]
    out_buf = jnp.zeros((m_total,) + mb_shape, x_microbatches.dtype)
    ring0 = jnp.zeros(mb_shape, x_microbatches.dtype)

    def tick(carry, t):
        ring, out_buf = carry
        # stage 0 feeds microbatch t (clamped); others take the ring value
        mb_idx = jnp.clip(t, 0, m_total - 1)
        feed = lax.dynamic_index_in_dim(x_microbatches, mb_idx, 0, keepdims=False)
        x_in = jnp.where(stage == 0, feed, ring)
        y = apply_stage(stacked_stage_params, x_in)
        # last stage: tick t produced microbatch t-(n_stages-1)
        out_idx = t - (n_stages - 1)
        valid = jnp.logical_and(stage == n_stages - 1,
                                jnp.logical_and(out_idx >= 0, out_idx < m_total))
        updated = lax.dynamic_update_index_in_dim(
            out_buf, y.astype(out_buf.dtype), jnp.clip(out_idx, 0, m_total - 1), 0)
        out_buf = jnp.where(valid, updated, out_buf)
        ring_next = lax.ppermute(y, axis_name, fwd_perm)
        return (ring_next, out_buf), None

    # initial carry must be marked pp-varying (the loop makes it so)
    ring0 = _pvary(ring0, axis_name)
    out_buf = _pvary(out_buf, axis_name)
    (_, out_buf), _ = lax.scan(tick, (ring0, out_buf), jnp.arange(ticks))
    return out_buf


def _f32_zeros_like(tree):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), tree)


def _pvary(tree, axes):
    """Mark every leaf as varying over ``axes`` (str or tuple; idempotent).

    Needed for params differentiated inside shard_map: AD transposes an
    unvarying→varying broadcast into an implicit psum over that axis, which
    would (a) sum per-stage cotangents before our masking and (b) double-
    count against the schedule's explicit dp reductions — marking the
    primals varying keeps every cross-device reduction explicit.
    """
    if isinstance(axes, str):
        axes = (axes,)

    def mark(v):
        for ax in axes:
            try:
                v = lax.pcast(v, ax, to="varying")
            except ValueError:
                continue  # already varying over ax — idempotent no-op
            except (AttributeError, TypeError):
                try:
                    v = lax.pvary(v, (ax,))
                except Exception:
                    pass
        return v
    return jax.tree_util.tree_map(mark, tree)


def _masked_add(acc, upd, valid):
    return jax.tree_util.tree_map(
        lambda a, u: a + jnp.where(valid, u.astype(a.dtype), 0), acc, upd)


def pipeline_train_1f1b(stage_params, stage_fwd: Callable, x_mb, y_mb, *,
                        axis_name: str = "pp", batch_axes=(),
                        embed_params=None, embed_fn: Callable = None,
                        head_params=None, head_loss_fn: Callable = None,
                        zero_bubble: bool = False):
    """TRUE 1F1B pipeline training step. Call inside ``shard_map``.

    Ref: ``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py``
    (1F1B) — here as an SPMD shifted-buffer schedule: at global tick ``t``
    stage ``s`` runs the forward of microbatch ``t - s`` and the backward of
    microbatch ``t - (2*(pp-1) - s)``; at the last stage a microbatch's
    backward fires on the SAME tick as its forward (that is the "1B after
    1F" property), and cotangents ride a reverse ``ppermute`` ring one stage
    per tick. Residuals (stage inputs) live in a ring of ``2*pp - 1`` slots
    — constant in M — and the backward slot recomputes the stage forward
    under ``jax.vjp`` (recompute-style 1F1B, the reference's
    recompute+1F1B mode).

    Args:
      stage_params: this stage's parameter pytree (sharded P("pp", ...)
        outside; inside shard_map it is the local stage's block).
      stage_fwd(stage_params, x) -> y: applies the whole local stage.
      x_mb: [M, mb, ...] microbatched stage-0 input (token ids if
        ``embed_fn`` is given, else already-embedded activations).
      y_mb: [M, mb, ...] per-microbatch labels, consumed at the last stage.
      embed_params/embed_fn(embed_params, tokens) -> activations: optional
        replicated pre-stage (embedding) evaluated at stage 0; its grads are
        returned replicated (psum over pp).
      head_params/head_loss_fn(head_params, y, labels) -> scalar mean loss:
        the loss head evaluated at the LAST stage. When ``head_loss_fn`` is
        None, ``y_mb`` must be unused and the loss is mean(y) (testing).

    Returns:
      (loss, dstage, dembed, dhead): scalar mean loss over all microbatches
      (replicated), fp32 grads for the local stage (P("pp", ...)), and
      replicated fp32 grads for embed/head params (``()`` where unused).

    ``zero_bubble`` (ZB-H1 style, see module docstring): stage ``s`` defers
    the WEIGHT-grad halves of its last ``pp-1-s`` microbatch backwards —
    those drain-chain hops compute dx ONLY (so the cotangent ring hop costs
    ``recompute+dx``, not ``recompute+dx+dw``) and the deferred dw's run in
    ``pp-1`` tail ticks off the critical path, from a saved ``(x, g)``
    queue of ``pp`` slots. Loss is bit-identical to 1F1B; grads equal up to
    fp32 accumulation order of the deferred terms.
    """
    pp = axis_size(axis_name)
    s = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    R = 2 * pp - 1                      # residual ring slots, M-independent
    T = M + 2 * (pp - 1) + ((pp - 1) if zero_bubble else 0)  # global ticks
    batch_axes = tuple(batch_axes)

    has_head = head_loss_fn is not None
    has_embed = embed_fn is not None
    if not has_head:
        head_params = ()
        head_loss_fn = lambda hp, y, lbl: jnp.mean(y)
    if not has_embed:
        embed_params = ()
        embed_fn = lambda ep, x: x
    # params must be varying over the pp and dp schedule axes before AD
    # (see _pvary). NOTE deliberately NOT over a tp axis: tp-sharded stage
    # leaves arrive varying from their in_specs, while tp-REPLICATED leaves
    # (norms) and the embed/head params stay unvarying — jax's vma-aware AD
    # then auto-psums their cross-member partial grads into the TRUE grad,
    # and activations/cotangents stay tp-invariant so no spurious psum
    # transposes are inserted (a varying-marked cotangent crossing the tp
    # psum transposes would double the grads).
    axes_all = (axis_name,) + batch_axes
    stage_params = _pvary(stage_params, axes_all)
    head_params = _pvary(head_params, axes_all)
    embed_params = _pvary(embed_params, axes_all)

    # activation shape: embed output of one microbatch
    act = jax.eval_shape(embed_fn, embed_params,
                         jax.eval_shape(lambda a: a[0], x_mb))
    act_shape, act_dtype = act.shape, act.dtype

    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
    bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
    is_last = s == pp - 1
    is_first = s == 0

    def loss_and_dy(y, labels):
        def f(yy, hp):
            return head_loss_fn(hp, yy, labels)
        (loss, (dy, dhead)) = jax.value_and_grad(f, argnums=(0, 1))(
            y, head_params)
        return loss, dy, dhead

    carry0 = dict(
        fwd_ring=jnp.zeros(act_shape, act_dtype),
        bwd_ring=jnp.zeros(act_shape, act_dtype),
        resid=jnp.zeros((R,) + act_shape, act_dtype),
        loss=jnp.zeros((), jnp.float32),
        # (p * 0) keeps each leaf's varying axes (tp-sharded leaves carry
        # tp-varying grads; fresh zeros would be unvarying and mismatch)
        dstage=jax.tree_util.tree_map(
            lambda p_: (p_ * 0).astype(jnp.float32), stage_params),
        dembed=_f32_zeros_like(embed_params),
        dhead=_f32_zeros_like(head_params),
    )
    if zero_bubble:
        # deferred weight-grad queue: (stage input, upstream cotangent)
        # pairs for the last pp-1-s microbatches, keyed m mod pp (the W
        # tick trails the B tick by pp-1-s < pp, so slots never collide)
        carry0["wq_x"] = jnp.zeros((pp,) + act_shape, act_dtype)
        carry0["wq_g"] = jnp.zeros((pp,) + act_shape, act_dtype)

    tree_add = lambda acc, upd: jax.tree_util.tree_map(
        lambda a, u: a + u.astype(a.dtype), acc, upd)

    def tick(c, t):
        # ---------------- forward slot: microbatch t - s ----------------
        m_f = t - s
        fwd_valid = jnp.logical_and(m_f >= 0, m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        tokens = lax.dynamic_index_in_dim(x_mb, m_f_c, 0, keepdims=False)
        # per-device branch: only stage 0 pays for the embedding gather
        # (inside shard_map the predicate is a local scalar, so lax.cond is
        # real control flow, not a both-sides select)
        x_in = lax.cond(is_first,
                        lambda: embed_fn(embed_params, tokens)
                        .astype(act_dtype),
                        lambda: c["fwd_ring"])
        y = stage_fwd(stage_params, x_in).astype(act_dtype)

        resid_new = lax.dynamic_update_index_in_dim(
            c["resid"], x_in, jnp.mod(m_f_c, R), 0)
        resid = jnp.where(fwd_valid, resid_new, c["resid"])

        # last stage only: loss + cotangent seed for this same microbatch
        # (head fwd+bwd is often the biggest op in the step — gate it)
        labels = lax.dynamic_index_in_dim(y_mb, m_f_c, 0, keepdims=False)
        take_loss = jnp.logical_and(is_last, fwd_valid)

        def head_branch(y, labels):
            loss_m, dy, dhead_m = loss_and_dy(y, labels)
            return (loss_m.astype(jnp.float32), dy.astype(act_dtype),
                    dhead_m)

        def head_skip(y, labels):
            return _pvary((jnp.zeros((), jnp.float32), jnp.zeros_like(y),
                           jax.tree_util.tree_map(jnp.zeros_like,
                                                  head_params)), axes_all)

        loss_m, dy, dhead_m = lax.cond(take_loss, head_branch, head_skip,
                                       y, labels)
        loss = c["loss"] + loss_m
        dhead = tree_add(c["dhead"], dhead_m)

        # ---------------- backward slot: microbatch t - (2(pp-1) - s) ----
        m_b = t - (2 * (pp - 1) - s)
        bwd_valid = jnp.logical_and(m_b >= 0, m_b < M)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        x_saved = lax.dynamic_index_in_dim(resid, jnp.mod(m_b_c, R), 0,
                                           keepdims=False)
        g = jnp.where(is_last, dy, c["bwd_ring"])
        if not zero_bubble:
            _, vjp_fn = jax.vjp(stage_fwd, stage_params, x_saved)
            dp, dx = vjp_fn(g.astype(act.dtype))
            dstage = _masked_add(c["dstage"], dp, bwd_valid)
        else:
            # ZB-H1: the last pp-1-s microbatches' backwards are on the
            # drain critical path — run dx ONLY there (dw deferred)
            d_s = (pp - 1) - s
            deferred = jnp.logical_and(bwd_valid, m_b >= M - d_s)
            p_zeros = jax.tree_util.tree_map(lambda p_: p_ * 0,
                                             stage_params)

            def bwd_full(x_in, gg):
                _, vjp_fn = jax.vjp(stage_fwd, stage_params, x_in)
                return vjp_fn(gg.astype(act.dtype))

            def bwd_dx_only(x_in, gg):
                _, vjp_x = jax.vjp(
                    lambda xx: stage_fwd(stage_params, xx), x_in)
                (dx_,) = vjp_x(gg.astype(act.dtype))
                return p_zeros, dx_

            dp, dx = lax.cond(deferred, bwd_dx_only, bwd_full, x_saved, g)
            dstage = _masked_add(c["dstage"], dp,
                                 jnp.logical_and(bwd_valid, ~deferred))
            wq_slot = jnp.mod(m_b_c, pp)
            wq_x = lax.dynamic_update_index_in_dim(
                c["wq_x"], x_saved, wq_slot, 0)
            wq_g = lax.dynamic_update_index_in_dim(
                c["wq_g"], g.astype(act_dtype), wq_slot, 0)
            keep = lambda new, old: jnp.where(deferred, new, old)
            wq_x, wq_g = keep(wq_x, c["wq_x"]), keep(wq_g, c["wq_g"])

            # ---- deferred W slot: tail ticks, off the critical path ----
            m_w = m_b - d_s
            w_valid = jnp.logical_and(m_w >= jnp.maximum(M - d_s, 0),
                                      m_w < M)
            m_w_c = jnp.clip(m_w, 0, M - 1)
            x_w = lax.dynamic_index_in_dim(wq_x, jnp.mod(m_w_c, pp), 0,
                                           keepdims=False)
            g_w = lax.dynamic_index_in_dim(wq_g, jnp.mod(m_w_c, pp), 0,
                                           keepdims=False)

            def w_branch(x_in, gg):
                _, vjp_p = jax.vjp(lambda p_: stage_fwd(p_, x_in),
                                   stage_params)
                (dpw,) = vjp_p(gg.astype(act.dtype))
                return dpw

            dpw = lax.cond(w_valid, w_branch, lambda x_in, gg: p_zeros,
                           x_w, g_w)
            dstage = _masked_add(dstage, dpw, w_valid)

        # stage 0's backward also flows into the embedding — gated likewise
        tokens_b = lax.dynamic_index_in_dim(x_mb, m_b_c, 0, keepdims=False)

        def embed_grad_branch(dx):
            _, evjp = jax.vjp(
                lambda ep: embed_fn(ep, tokens_b).astype(act_dtype),
                embed_params)
            (dembed_m,) = evjp(dx)
            return dembed_m

        def embed_grad_skip(dx):
            return _pvary(jax.tree_util.tree_map(jnp.zeros_like,
                                                 embed_params), axes_all)

        dembed_m = lax.cond(jnp.logical_and(is_first, bwd_valid),
                            embed_grad_branch, embed_grad_skip, dx)
        dembed = tree_add(c["dembed"], dembed_m)

        # ---------------- ring handoffs ----------------
        fwd_ring = lax.ppermute(y, axis_name, fwd_perm)
        bwd_ring = lax.ppermute(dx.astype(act_dtype), axis_name, bwd_perm)
        out = dict(fwd_ring=fwd_ring, bwd_ring=bwd_ring, resid=resid,
                   loss=loss, dstage=dstage, dembed=dembed, dhead=dhead)
        if zero_bubble:
            out["wq_x"], out["wq_g"] = wq_x, wq_g
        return out, None

    # the loop makes every carry leaf pp(+dp)-varying; mark the init so
    carry0 = _pvary(carry0, axes_all)
    c, _ = lax.scan(tick, carry0, jnp.arange(T))

    inv_m = 1.0 / M
    loss = lax.psum(c["loss"], axis_name) * inv_m
    scale = lambda tr: jax.tree_util.tree_map(lambda g: g * inv_m, tr)
    dstage = scale(c["dstage"])
    dembed = jax.tree_util.tree_map(
        lambda g: lax.psum(g, axis_name), scale(c["dembed"]))
    dhead = jax.tree_util.tree_map(
        lambda g: lax.psum(g, axis_name), scale(c["dhead"]))
    if batch_axes:
        # data parallelism over the microbatch's batch dim: every grad and
        # the loss are per-dp-shard means — average across the dp group
        nb = 1
        for a in batch_axes:
            nb *= axis_size(a)
        pmean = lambda v: lax.psum(v, batch_axes) / nb
        loss = pmean(loss)
        dstage = jax.tree_util.tree_map(pmean, dstage)
        dembed = jax.tree_util.tree_map(pmean, dembed)
        dhead = jax.tree_util.tree_map(pmean, dhead)
    return loss, dstage, dembed, dhead


def pipeline_train_step(pipe: "PipelineLayer", mesh, x, y, *,
                        layer_call: Callable = None,
                        head_loss_fn: Callable = None, head_params=None,
                        embed_fn: Callable = None, embed_params=None,
                        batch_axes=(), stage_specs=None,
                        schedule: str = "1f1b"):
    """1F1B loss+grads for a PipelineLayer under ``mesh`` (pp axis).
    ``schedule``: "1f1b" (default) or "zb1" (zero-bubble W-split drain —
    see ``pipeline_train_1f1b(zero_bubble=True)``).

    Splits the batch into ``pipe.num_microbatches``, runs the 1F1B schedule
    in a ``shard_map`` over the pp axis, and returns
    ``(loss, stacked_grads, dembed, dhead)`` — grads are fp32, stacked
    grads sharded P("pp", ...) exactly like the params, embed/head grads
    replicated (``None`` when the corresponding part was not given).

    ``batch_axes`` (e.g. ``("dp",)``) composes pp with data parallelism:
    each microbatch's batch dim is sharded across the dp group, every dp
    member runs the same pipeline on its shard, and loss/grads are
    dp-averaged inside the shard_map.
    """
    from jax import shard_map

    if schedule not in ("1f1b", "zb1"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(expected '1f1b' or 'zb1')")
    layer_call = layer_call or (lambda lyr, h: lyr(h))
    mb_n = pipe.num_microbatches
    b = x.shape[0]
    assert b % mb_n == 0, \
        f"num_microbatches ({mb_n}) must divide the batch size ({b})"
    xm = x.reshape((mb_n, b // mb_n) + x.shape[1:])
    ym = y.reshape((mb_n, b // mb_n) + y.shape[1:])

    has_embed = embed_fn is not None
    has_head = head_loss_fn is not None
    embed_params = embed_params if has_embed else ()
    head_params = head_params if has_head else ()

    batch_axes = tuple(batch_axes)
    mb_axis = batch_axes if batch_axes else None
    # stage_specs override: tp-aware per-leaf specs (e.g. llama_tp_stage_specs)
    pspec = stage_specs if stage_specs is not None else pipe.stage_specs()
    rep = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
    xspec = P(None, mb_axis, *(None,) * (xm.ndim - 2))
    yspec = P(None, mb_axis, *(None,) * (ym.ndim - 2))

    def stage_fwd(stage_params, h):
        def body(hh, lyr):
            return layer_call(lyr, hh), None
        run = lambda p, v: lax.scan(body, v, p)[0]
        if pipe.remat:
            run = jax.checkpoint(run)
        return run(stage_params, h)

    # check_vma off (here and in PipelineLayer.forward): the stage body
    # runs the layer call, whose flash and rms Pallas calls on a TPU carry
    # no varying-axes annotation, which the checker refuses
    @functools.partial(
        shard_map, mesh=mesh.mesh,
        in_specs=(pspec, xspec, yspec, rep(embed_params), rep(head_params)),
        out_specs=(P(), pspec, rep(embed_params), rep(head_params)),
        check_vma=False)
    def run(stage_params, xm, ym, embed_params, head_params):
        return pipeline_train_1f1b(
            stage_params, stage_fwd, xm, ym, batch_axes=batch_axes,
            embed_params=embed_params, embed_fn=embed_fn,
            head_params=head_params, head_loss_fn=head_loss_fn,
            zero_bubble=(schedule == "zb1"))

    loss, dstage, dembed, dhead = run(pipe.stacked, xm, ym,
                                      embed_params, head_params)
    return (loss, dstage,
            dembed if has_embed else None, dhead if has_head else None)


class PipelineLayer(Module):
    """Reference-named wrapper: partitions identical blocks over pp stages.

    Single-program: under a mesh with pp>1 the stacked weights shard
    P("pp", ...); without a mesh it runs the plain sequential loop.
    """

    def __init__(self, layers: list[Module], num_stages: int,
                 num_microbatches: int = 1, remat: bool = True):
        super().__init__()
        assert len(layers) % num_stages == 0, \
            f"num_stages ({num_stages}) must divide len(layers) ({len(layers)})"
        self.stacked = stack_layers(layers)
        self.template = layers[0]
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.layers_per_stage = len(layers) // num_stages
        self.n_layers = len(layers)
        self.remat = remat
        # leading axis is the stage axis
        flat, _ = jax.tree_util.tree_flatten(self.stacked)

    @classmethod
    def from_stacked(cls, stacked, *, n_layers: int, num_stages: int,
                     num_microbatches: int = 1, remat: bool = True):
        """Build from an ALREADY-STACKED [L, ...] layer pytree (e.g. the
        canonical param tree of a jitted training loop) with the same
        invariants as __init__."""
        assert n_layers % num_stages == 0, \
            f"num_stages ({num_stages}) must divide n_layers ({n_layers})"
        self = cls.__new__(cls)
        Module.__init__(self)
        self.stacked = stacked
        self.template = None
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.layers_per_stage = n_layers // num_stages
        self.n_layers = n_layers
        self.remat = remat
        return self

    def stage_specs(self):
        """PartitionSpecs: leading (layer) axis on pp."""
        def spec(leaf):
            return P(*(("pp",) + (None,) * (leaf.ndim - 1)))
        return jax.tree_util.tree_map(spec, self.stacked)

    def __call__(self, x, layer_call: Callable = None, mesh=None):
        layer_call = layer_call or (lambda lyr, h: lyr(h))
        if mesh is None or mesh.pp == 1:
            def body(h, lyr_params):
                return layer_call(lyr_params, h), None
            out, _ = lax.scan(body, x, self.stacked)
            return out
        from jax import shard_map
        mb = self.num_microbatches
        b = x.shape[0]
        assert b % mb == 0, "batch must divide microbatches"
        xm = x.reshape((mb, b // mb) + x.shape[1:])

        pspec = self.stage_specs()
        data_spec = P(*((None,) * xm.ndim))

        @functools.partial(
            shard_map, mesh=mesh.mesh,
            in_specs=(pspec, data_spec), out_specs=data_spec,
            check_vma=False)
        def run(stage_params, xm):
            out = pipeline_apply(stage_params, layer_call, xm,
                                 axis_name="pp",
                                 layers_per_stage=self.layers_per_stage,
                                 remat=self.remat)
            # broadcast last stage's result to all pp members so downstream
            # (loss) is replicated over pp: zero elsewhere + psum
            n = axis_size("pp")
            is_last = (lax.axis_index("pp") == n - 1).astype(out.dtype)
            return lax.psum(out * is_last, "pp")
        return run(self.stacked, xm).reshape(x.shape)
