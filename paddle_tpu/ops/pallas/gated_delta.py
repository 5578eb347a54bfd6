"""The gated delta rule (Gated DeltaNet): the chunk kernel, its twin, the step.

For one head, with a state ``S`` in ``R^{d_k x d_v}`` (float32), a token's
normed key ``k`` and query ``q`` (``d_k``), value ``v`` (``d_v``), decay
``alpha = exp(g)`` in (0, 1] and write strength ``beta`` in [0, 2]::

    S' = alpha S
    S  = S' + beta k (v - k^T S')^T        (= alpha (I - beta k k^T) S + beta k v^T)
    o  = S^T q

``gated_delta_chunk`` runs a row of tokens from an incoming state to an
outgoing one in chunks of ``C`` tokens: inside a chunk the products
``K K^T`` and ``Q K^T``, the inverse of the unit lower-triangular
``I + strict_lower(diag(beta) (K K^T) * decay)`` (the WY form of the
chunk's Householder-like product), and the hand-over ``S <- decay S +
K_d^T V_new``; the state stays in VMEM across a row's chunks, so it moves
through HBM once a call and not once a token. ``gated_delta_step`` is the
recurrence itself for one token a slot, left to XLA (no kernel: see there).

A token past its row's length (``lens``) is handed over as ``g = 0`` and
``beta = 0``: it leaves the state as it was. The chunk kernel walks live
chunks only (``pl.when`` on the row's length, the dead chunks' blocks
pinned to the last live one so that no copy is made for them).

The kernel is one ``pallas_call`` with its ``name=`` under one ``jit`` a
program (tracing a call site is the larger cost of a kernel, and a
program has one site a linear layer). Its dispatcher chooses from what the
trace can observe (``mosaic_kernels_apply``): on one TPU the kernel, with
no fallback; off the TPU and under a multi-device mesh the ``_xla`` twin,
which is the same arithmetic in ``jax.numpy``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import mosaic_kernels_apply
from paddle_tpu.ops.pallas.paged_attention import _note_trace

CHUNK = 64            # tokens a chunk: one [C, C] solve and four MXU tiles
_BASE = 8             # diagonal blocks inverted by their Neumann product
_HEADS_PER_STEP = 6   # independent chains the scheduler may interleave


def _heads_per_step(h: int) -> int:
    return max(d for d in range(1, _HEADS_PER_STEP + 1) if h % d == 0)


# ------------------------------------------------------------ the solve
def _unit_lower_inverse(a, dot):
    """``(I + a)^-1`` for ``a`` [C, C] strictly lower triangular, exactly
    (every term of the finite series once), by products alone: the
    ``_BASE``-wide diagonal blocks by ``(I + n)(I + n^2)(I + n^4)`` with
    ``n = -a`` (``n^8 = 0`` there), then pairs of blocks merged, level by
    level, by ``T <- T - T (a * off) T``, ``off`` the lower-left block of
    each pair. Terms grow at most as in a ``_BASE``-wide block, whatever
    ``C`` is: the Neumann product over the whole of ``a`` would square
    terms of 1e27 for an answer of 1 where keys repeat under ``beta`` 2."""
    c = a.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (i == j).astype(a.dtype)
    n = jnp.where(i // _BASE == j // _BASE, -a, 0.0)
    t = eye + n
    width = 2
    while width < min(_BASE, c):
        n = dot(n, n)
        t = t + dot(t, n)
        width *= 2
    s = _BASE
    while s < c:
        off = (i // (2 * s) == j // (2 * s)) & (i // s != j // s)
        t = t - dot(dot(t, jnp.where(off, a, 0.0)), t)
        s *= 2
    return t


def _chunk_math(q, k, kt, v, g_row, g_col, b_col, g_last, s, dot, big):
    """One chunk of one head: -> (o [C, d_v] f32, the state after it).
    ``kt`` is ``k`` transposed, ``g_*`` the cumulative log-decay inside the
    chunk as a row [1, C] and a column [C, 1], ``b_col`` beta as a column,
    ``g_last`` the chunk's whole log-decay (a scalar: Mosaic broadcasts
    along one axis at a time, so it is not sliced from ``g_row``);
    ``dot`` multiplies in float32, ``big`` as the kernel multiplies what
    only the chunk's output reads (bfloat16 operands; q, k arrive so)."""
    c = q.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # decay from token j to token i >= j; above the diagonal the exponent
    # is positive and unbounded, so it is masked before the exp
    decay = jnp.exp(jnp.where(i >= j, g_col - g_row, -jnp.inf))
    a = jnp.where(i > j, b_col * big(k, kt) * decay, 0.0)
    t = _unit_lower_inverse(a, dot)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    w = dot(t, kf * (b_col * jnp.exp(g_col)))
    u = dot(t, vf * b_col)
    # what the state is made of stays float32: u and w s nearly cancel
    # where the state already holds the key, and a row's state is summed
    # over hundreds of chunks; ``big`` feeds this chunk's output alone
    v_new = u - dot(w, s)
    attn = jnp.where(i >= j, big(q, kt) * decay, 0.0)
    o = big(q.astype(jnp.float32) * jnp.exp(g_col), s) + big(attn, v_new)
    s = s * jnp.exp(jnp.full((1, s.shape[1]), g_last)) + dot(
        kt.astype(jnp.float32) * jnp.exp(g_last - g_row), v_new)
    return o, s


def _f32_dot(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _bf16_dot(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


# ----------------------------------------------------- the chunk kernel
def _chunk_kernel(live_ref, total_ref, q_ref, k_ref, kt_ref, v_ref, rows_ref,
                  cols_ref, s_in_ref, o_ref, s_out_ref, s_scr, *, heads,
                  chunk):
    b, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = pl.num_programs(2)
    h0 = (b * pl.num_programs(1) + hg) * heads

    @pl.when(c == 0)
    def _():
        s_scr[...] = s_in_ref[...]

    @pl.when(c * chunk < live_ref[b])
    def _():
        for h in range(heads):
            o, s = _chunk_math(
                q_ref[h], k_ref[h], kt_ref[h], v_ref[h],
                rows_ref[h], cols_ref[h, :, 0:1], cols_ref[h, :, 1:2],
                total_ref[(h0 + h) * n + c], s_scr[h], _f32_dot, _bf16_dot)
            o_ref[h] = o.astype(o_ref.dtype)
            s_scr[h] = s

    @pl.when(c * chunk >= live_ref[b])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c == n - 1)
    def _():
        s_out_ref[...] = s_scr[...]


def _chunked(x, n, c):
    """[B, T, H, d] -> [B, H, N, C, d]."""
    b, _, h, d = x.shape
    return x.reshape(b, n, c, h, d).transpose(0, 3, 1, 2, 4)


def _chunk_inputs(q, k, v, g, beta, lens, chunk):
    """What both forms of the chunked rule are handed: the row padded to
    whole chunks, dead tokens as ``g = 0, beta = 0``, ``g`` summed inside
    each chunk, everything chunk-major a head."""
    b, t, h, _ = q.shape
    n = -(-t // chunk)
    pad = n * chunk - t
    live = jnp.arange(n * chunk)[None, :] < lens[:, None]          # [B, T']

    def prep(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    g = jnp.where(live[..., None], prep(g.astype(jnp.float32)), 0.0)
    beta = jnp.where(live[..., None], prep(beta.astype(jnp.float32)), 0.0)
    g = jnp.cumsum(g.reshape(b, n, chunk, h), axis=2)
    cols = jnp.stack([g, beta.reshape(b, n, chunk, h)],
                     axis=-1).transpose(0, 3, 1, 2, 4)           # [B,H,N,C,2]
    rows = g.transpose(0, 3, 1, 2)[:, :, :, None, :]             # [B,H,N,1,C]
    total = g[:, :, -1].transpose(0, 2, 1)                       # [B,H,N]
    qc, kc, vc = (_chunked(prep(x), n, chunk) for x in (q, k, v))
    return qc, kc, vc, rows, cols, total, n


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gated_delta_chunk_call(q, k, v, g, beta, state, lens, *, chunk,
                            interpret):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    qc, kc, vc, rows, cols, total, n = _chunk_inputs(q, k, v, g, beta, lens,
                                                     chunk)
    ktc = kc.swapaxes(-1, -2)                                    # [B,H,N,dk,C]
    hb = _heads_per_step(h)

    def at(bi, hi, ci, live, _):
        # a dead chunk's block is the last live one's: no copy is made
        last = jnp.maximum(-(-live[bi] // chunk) - 1, 0)
        return bi, hi, jnp.minimum(ci, last), 0, 0

    tok = lambda *tail: pl.BlockSpec((None, hb, None) + tail, at)
    st = pl.BlockSpec((None, hb, dk, dv), lambda bi, hi, ci, *_:
                      (bi, hi, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, h // hb, n),
            in_specs=[tok(chunk, dk), tok(chunk, dk), tok(dk, chunk),
                      tok(chunk, dv), tok(1, chunk), tok(chunk, 2), st],
            out_specs=[pl.BlockSpec(
                (None, hb, None, chunk, dv),
                lambda bi, hi, ci, *_: (bi, hi, ci, 0, 0)), st],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, n, chunk, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 8 (after the two prefetched): the state, in place
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret, name="gated_delta_chunk",
    )(lens.astype(jnp.int32), total.reshape(-1), qc, kc, ktc, vc, rows, cols,
      state.astype(jnp.float32))
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, n * chunk, h, dv)[:, :t]
    return o, state


def gated_delta_chunk_pallas(q, k, v, g, beta, state, lens, *, chunk=CHUNK,
                             interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gated_delta_chunk_call(q, k, v, g, beta, state, lens,
                                   chunk=int(chunk), interpret=bool(interpret))


def gated_delta_chunk_xla(q, k, v, g, beta, state, lens, *, chunk=CHUNK):
    """The chunked rule in ``jax.numpy``: the kernel's arithmetic, float32
    throughout, a ``lax.scan`` over a row's chunks."""
    b, t, h, _ = q.shape
    qc, kc, vc, rows, cols, total, n = _chunk_inputs(q, k, v, g, beta, lens,
                                                     chunk)

    def head(qh, kh, vh, rh, ch, th, s):      # one row's one head: [N, ...]
        def step(s, x):
            qi, ki, vi, ri, ci, ti = x
            o, s = _chunk_math(qi, ki, ki.T, vi, ri, ci[:, 0:1], ci[:, 1:2],
                               ti, s, _f32_dot, _f32_dot)
            return s, o
        s, o = jax.lax.scan(step, s, (qh, kh, vh, rh, ch, th))
        return o, s

    o, state = jax.vmap(jax.vmap(head))(qc, kc, vc, rows, cols, total,
                                        state.astype(jnp.float32))
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, n * chunk, h, -1)[:, :t]
    return o, state


def gated_delta_chunk(q, k, v, g, beta, state, lens, *, chunk=CHUNK):
    """q, k [B, T, H, d_k] (normed, q scaled); v [B, T, H, d_v]; g (log
    alpha <= 0), beta [B, T, H]; state [B, H, d_k, d_v] float32; lens [B]
    live tokens a row -> (o [B, T, H, d_v] float32, the state after each
    row's live tokens)."""
    if mosaic_kernels_apply():
        _note_trace("gated_delta_chunk:pallas")
        return gated_delta_chunk_pallas(q, k, v, g, beta, state, lens,
                                        chunk=chunk, interpret=False)
    _note_trace("gated_delta_chunk:xla")
    return gated_delta_chunk_xla(q, k, v, g, beta, state, lens, chunk=chunk)


# ------------------------------------------------------------- the step
def gated_delta_step(q, k, v, g, beta, state, active):
    """One token a slot: q, k [S, H, d_k]; v [S, H, d_v]; g, beta [S, H];
    state [S, H, d_k, d_v] float32 (donated with the cache, so updated
    where it lies); active [S] bool (a slot that does not run is handed
    over as alpha 1 and beta 0: it keeps its state) -> (o [S, H, d_v]
    float32, state).

    XLA's own form on every backend: the equations as they are written,
    products by broadcasting and sums along an axis, float32 throughout (no
    dot: nothing for the matmul unit's precision to round). A Pallas kernel
    of this step was measured against it on the chip at the cell's shape (8
    slots, 30 heads, 12 layers a tick): 1.519 ms against 1.222, of a floor
    of 0.519 for the states' bytes, so the kernel went (PERF.md, section 6,
    PR 35)."""
    run = active[:, None]
    alpha = jnp.where(run, jnp.exp(g.astype(jnp.float32)), 1.0)
    beta = jnp.where(run, beta.astype(jnp.float32), 0.0)
    f = lambda x: x.astype(jnp.float32)
    q, k, v = f(q), f(k), f(v)
    s = alpha[:, :, None, None] * f(state)                # S' = alpha S
    delta = beta[:, :, None] * (v - (k[..., None] * s).sum(2))
    s = s + k[..., None] * delta[:, :, None, :]
    return (q[..., None] * s).sum(2), s


def clear_caches():
    _gated_delta_chunk_call.clear_cache()
