"""Autoregressive generation with a static KV cache (ref capability:
``fused_multi_transformer`` inference kernels + PaddleNLP ``generate()``).

TPU-first: the decode loop is a ``lax.while_loop`` over a PRE-ALLOCATED
[B, max_len, H, D] cache — static shapes, one compiled program for the whole
generation, cache updated via dynamic_update_slice (no recompiles per step,
unlike naive eager decoding). Prefill and decode are the same jitted fn.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import attention as A
from paddle_tpu.quantization import wo_matmul


@dataclass
class KVCache:
    """Per-layer [B, cap, H_kv, D] k/v buffers + current length.

    With ``window`` set (sliding-window models), the cache is a RING of
    ``cap = min(max_len, window)`` slots: writes land at ``pos % cap`` and
    ``slot_pos`` tracks each slot's absolute position for masking — decode
    memory is bounded by the window, not the generation length."""
    k: list
    v: list
    length: jnp.ndarray  # scalar int32
    slot_pos: object = None  # [cap] int32 absolute positions, or None

    @staticmethod
    def init(num_layers, batch, max_len, num_kv_heads, head_dim, dtype,
             window=None):
        cap = max_len if window is None else min(max_len, window)
        z = lambda: jnp.zeros((batch, cap, num_kv_heads, head_dim), dtype)
        slot_pos = None if window is None else jnp.full((cap,), -1, jnp.int32)
        return KVCache([z() for _ in range(num_layers)],
                       [z() for _ in range(num_layers)],
                       jnp.zeros((), jnp.int32), slot_pos)


jax.tree_util.register_pytree_node(
    KVCache,
    lambda c: ((c.k, c.v, c.length, c.slot_pos), None),
    lambda aux, ch: KVCache(*ch))


def _attend_with_cache(q, k_cache, v_cache, new_k, new_v, pos,
                       window=None, slot_pos=None):
    """Write new_k/new_v at pos, attend q over the cache. ``window`` keeps
    decode consistent with sliding-window training (Mistral). With
    ``slot_pos`` the cache is a ring of ``cap`` slots: writes wrap at
    ``pos % cap`` and masking uses each slot's absolute position."""
    sq = q.shape[1]
    cap = k_cache.shape[1]
    q_idx = pos + jnp.arange(sq)[:, None]
    if slot_pos is not None:
        if sq > 1:
            # prefill: the whole chunk is in hand — attend over it directly
            # (the ring may be smaller than the chunk, so early queries'
            # keys would already be evicted); then keep only the last cap
            # positions in the ring for decode.
            # The chunk-local attention below IGNORES pre-existing ring
            # contents, so resuming/chunked prefill over a non-empty ring
            # would be silently wrong — require a statically-known pos==0
            # (generate()/beam_search prefill with a literal 0).
            if not (isinstance(pos, int) and pos == 0):
                raise NotImplementedError(
                    "ring-cache (windowed) prefill requires static pos==0; "
                    f"got pos={pos!r}. Chunked prefill over an existing "
                    "ring cache is not supported — prefill the whole "
                    "prompt at once.")
            a = jnp.arange(sq)
            keep = a[:, None] >= a[None, :]
            if window is not None:
                keep &= (a[:, None] - a[None, :]) < window
            out = A.xla_attention(q, new_k, new_v, attn_mask=keep[None, None])
            tail = min(sq, cap)
            tail_pos = pos + jnp.arange(sq - tail, sq)
            idx = tail_pos % cap
            k_cache = k_cache.at[:, idx].set(new_k[:, sq - tail:])
            v_cache = v_cache.at[:, idx].set(new_v[:, sq - tail:])
            return out, k_cache, v_cache
        idx = (pos + jnp.arange(sq)) % cap
        k_cache = k_cache.at[:, idx].set(new_k)
        v_cache = v_cache.at[:, idx].set(new_v)
        key_abs = slot_pos[None, :]  # [1, cap] (already updated by caller)
        keep = (key_abs >= 0) & (key_abs <= q_idx)
        if window is not None:
            keep &= (q_idx - key_abs) < window
    else:
        k_cache = lax.dynamic_update_slice_in_dim(k_cache, new_k, pos, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(v_cache, new_v, pos, axis=1)
        # mask: key index must be <= query absolute position (and in-window)
        key_idx = jnp.arange(cap)[None, :]
        keep = key_idx <= q_idx
        if window is not None:
            keep &= (q_idx - key_idx) < window
    mask = keep[None, None]  # [1,1,Sq,cap]
    out = A.xla_attention(q, k_cache, v_cache, attn_mask=mask)
    return out, k_cache, v_cache


def llama_forward_with_cache(model, input_ids, cache: KVCache, pos):
    """One forward over `input_ids` (prefill chunk or single token)."""
    cfg = model.cfg
    x = jnp.take(model.model.embed_tokens, input_ids, axis=0)
    d = cfg.hidden_size // cfg.num_attention_heads
    positions = pos + jnp.arange(input_ids.shape[1])
    # rope scaling: linear/ntk are static; dynamic-NTK rides the TRACED
    # current length (pos + chunk), matching HF generation semantics
    # (earlier cache entries keep the base they were rotated with)
    cos, sin = A.rope_cos_sin(input_ids.shape[1], d, base=cfg.rope_theta,
                              position_ids=positions,
                              scaling=getattr(cfg, "rope_scaling", None),
                              max_position_embeddings=getattr(
                                  cfg, "max_position_embeddings", None),
                              cur_len=pos + input_ids.shape[1],
                              allow_dynamic=False)
    slot_pos = cache.slot_pos
    if slot_pos is not None:  # ring cache: record absolute slot positions
        cap = slot_pos.shape[0]
        s = input_ids.shape[1]
        tail = min(s, cap)  # prefill writes only the last cap positions
        tail_pos = positions[s - tail:]
        slot_pos = slot_pos.at[tail_pos % cap].set(tail_pos)
    new_k_list, new_v_list = [], []
    for li, lyr in enumerate(model.model.layers):
        h = lyr.input_layernorm(x)
        b, s, _ = h.shape
        att = lyr.self_attn
        qkv = wo_matmul(h, att.qkv_proj)
        if getattr(att, "qkv_bias", None) is not None:  # Qwen2
            qkv = qkv + att.qkv_bias
        nh, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
        q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
        q = A.apply_rope(q.reshape(b, s, nh, hd), cos, sin)
        k = A.apply_rope(k.reshape(b, s, nkv, hd), cos, sin)
        v = v.reshape(b, s, nkv, hd)
        out, k_c, v_c = _attend_with_cache(q, cache.k[li], cache.v[li],
                                           k, v, pos,
                                           window=getattr(cfg, "sliding_window",
                                                          None),
                                           slot_pos=slot_pos)
        new_k_list.append(k_c)
        new_v_list.append(v_c)
        x = x + wo_matmul(out.reshape(b, s, nh * hd), att.o_proj)
        x = x + lyr.mlp(lyr.post_attention_layernorm(x))
    x = model.model.norm(x)
    logits = model.logits(x)
    new_cache = KVCache(new_k_list, new_v_list, pos + input_ids.shape[1],
                        slot_pos)
    return logits, new_cache


def _apply_repetition_penalty(logits, appeared, penalty):
    """CTRL-style penalty (ref PaddleNLP GenerationMixin): divide positive
    scores / multiply negative scores of already-generated tokens."""
    if penalty == 1.0:
        return logits
    penalised = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(appeared, penalised, logits)


def _sample(logits, rng, temperature, top_k, top_p):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def _sample_rows(logits, rng, temps, top_ps, top_k=None, bias=None):
    """Per-ROW temperature/top-p sampling (the serving engine's
    per-request params; ref PaddleNLP predictor per-request
    GenerationConfig). ``temps``/``top_ps``: [B] traced — temperature 0
    means greedy FOR THAT ROW; top_p 1.0 disables the nucleus cut.
    ``top_k`` stays global/static. ``bias`` ([B, V] additive, 0 / -1e30)
    is the grammar-constraint mask (ISSUE 14): added BEFORE either
    branch, so both the stochastic and the greedy rows can only pick
    mask-legal tokens.

    One ``lax.cond`` on what the call can see of its rows: where no
    temperature is above 0 the call IS ``argmax(logits + bias)`` and
    nothing else runs; the scale, the full-vocabulary sort, softmax,
    cumulative sum and the Gumbel draw run only when some row samples
    (its greedy rows still take the argmax). ``rng`` is the caller's
    whichever branch runs, so the host's key sequence does not depend
    on the branch. A caller with rows that do not run (a freed slot
    keeps its last temperature) passes 0 for them."""
    if bias is not None:
        logits = logits + bias

    def greedy():
        return jnp.argmax(logits, axis=-1)

    def stochastic():
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
        scaled = logits / safe_t
        if top_k is not None and top_k > 0:
            kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
            scaled = jnp.where(scaled < kth, -1e30, scaled)
        sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_ps[:, None], axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        scaled = jnp.where(scaled < cutoff, -1e30, scaled)
        sampled = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(temps > 0, sampled, greedy())

    return lax.cond(jnp.any(temps > 0), stochastic, greedy)


def generate(model, input_ids, max_new_tokens=32, temperature=0.0, top_k=None,
             top_p=None, eos_token_id=None, rng=None, repetition_penalty=1.0,
             min_new_tokens=0):
    """Greedy/temperature/top-k/top-p decoding (ref PaddleNLP GenerationMixin)
    with repetition penalty and min-length constraint.

    One jitted while_loop; returns [B, prompt+max_new_tokens].
    """
    cfg = model.cfg
    b, prompt_len = input_ids.shape
    max_len = prompt_len + max_new_tokens
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    cache = KVCache.init(cfg.num_hidden_layers, b, max_len,
                         cfg.num_key_value_heads,
                         cfg.hidden_size // cfg.num_attention_heads, cfg.dtype,
                         window=getattr(cfg, "sliding_window", None))

    def constrain(logits, appeared, gen_len):
        logits = _apply_repetition_penalty(logits, appeared, repetition_penalty)
        if eos_token_id is not None and min_new_tokens > 0:
            logits = jnp.where(
                (gen_len < min_new_tokens)
                & (jnp.arange(logits.shape[-1]) == eos_token_id)[None, :],
                -1e30, logits)
        return logits

    @jax.jit
    def run(model, input_ids, cache, rng):
        vocab = cfg.vocab_size
        appeared = jnp.zeros((b, vocab), bool)
        appeared = appeared.at[jnp.arange(b)[:, None], input_ids].set(True)
        logits, cache = llama_forward_with_cache(model, input_ids, cache, 0)
        logits = constrain(logits[:, -1].astype(jnp.float32), appeared, 0)
        next_tok = _sample(logits, rng, temperature, top_k, top_p)
        appeared = appeared.at[jnp.arange(b), next_tok].set(True)
        tokens = jnp.concatenate(
            [input_ids, jnp.zeros((b, max_new_tokens), input_ids.dtype)], axis=1)
        tokens = tokens.at[:, prompt_len].set(next_tok)
        done = jnp.zeros((b,), bool) if eos_token_id is None else (next_tok == eos_token_id)

        def cond(state):
            i, tokens, cache, rng, done, appeared = state
            return jnp.logical_and(i < max_new_tokens - 1, ~jnp.all(done))

        def body(state):
            i, tokens, cache, rng, done, appeared = state
            rng, sub = jax.random.split(rng)
            cur = lax.dynamic_slice_in_dim(tokens, prompt_len + i, 1, axis=1)
            logits, cache = llama_forward_with_cache(model, cur, cache, prompt_len + i)
            logits = constrain(logits[:, -1].astype(jnp.float32), appeared, i + 1)
            nxt = _sample(logits, sub, temperature, top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            appeared = appeared.at[jnp.arange(b), nxt].set(True)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, None], prompt_len + i + 1, axis=1)
            return (i + 1, tokens, cache, rng, done, appeared)

        state = (jnp.zeros((), jnp.int32), tokens, cache, rng, done, appeared)
        _, tokens, _, _, _, _ = lax.while_loop(cond, body, state)
        return tokens

    return run(model, input_ids, cache, rng)


def beam_select(running_lp, seqs, fin_seqs, fin_scores, logp, i,
                prompt_len, eos_token_id, length_penalty):
    """One beam expansion: place token i, split 2K candidates into
    finished (eos) and running pools. Shapes: running_lp/fin_scores
    [B, K], seqs/fin_seqs [B, K, L], logp [B, K, V]. Shared by the
    static-cache beam_search AND the paged beam (models/paged.py) so
    their selection math can never drift apart."""
    b, K = running_lp.shape
    V = logp.shape[-1]
    NEG = jnp.float32(-1e9)
    total = running_lp[:, :, None] + logp  # [B, K, V]
    cand_lp, cand_idx = lax.top_k(total.reshape(b, K * V), 2 * K)
    beam = cand_idx // V  # [B, 2K]
    tok = cand_idx % V
    cand_seqs = jnp.take_along_axis(seqs, beam[:, :, None], axis=1)
    cand_seqs = cand_seqs.at[:, :, prompt_len + i].set(tok)

    if eos_token_id is not None:
        is_eos = tok == eos_token_id
    else:
        is_eos = jnp.zeros_like(tok, bool)
    # finished pool: merge newly-finished candidates, keep top K
    cand_score = cand_lp / ((i + 1.0) ** length_penalty)
    all_scores = jnp.concatenate(
        [fin_scores, jnp.where(is_eos, cand_score, NEG)], axis=1)
    all_seqs = jnp.concatenate([fin_seqs, cand_seqs], axis=1)
    fin_scores, fin_idx = lax.top_k(all_scores, K)
    fin_seqs = jnp.take_along_axis(all_seqs, fin_idx[:, :, None], axis=1)

    # running pool: best K non-eos candidates
    run_lp_cand = jnp.where(is_eos, NEG, cand_lp)
    running_lp, run_idx = lax.top_k(run_lp_cand, K)
    seqs = jnp.take_along_axis(cand_seqs, run_idx[:, :, None], axis=1)
    new_beam = jnp.take_along_axis(beam, run_idx, axis=1)  # [B, K]
    new_tok = jnp.take_along_axis(tok, run_idx, axis=1)
    return running_lp, seqs, fin_seqs, fin_scores, new_beam, new_tok


def beam_search(model, input_ids, max_new_tokens=32, num_beams=4,
                length_penalty=1.0, eos_token_id=None):
    """Beam search with a beam-gathered KV cache (ref: PaddleNLP
    ``GenerationMixin.beam_search`` / ``BeamSearchScorer``).

    TPU-native: beams live in a [B*K] leading dim so every step is one
    batched forward; beam reordering is a gather on the cache pytree inside
    ``lax.scan`` — static shapes, single compile.

    Returns (sequences [B, prompt+max_new], scores [B]) — the best finished
    hypothesis per batch (length-penalised log prob, PaddleNLP convention
    ``sum logp / len**alpha``).
    """
    cfg = model.cfg
    b, prompt_len = input_ids.shape
    K, V = num_beams, cfg.vocab_size
    max_len = prompt_len + max_new_tokens
    NEG = jnp.float32(-1e9)

    cache = KVCache.init(cfg.num_hidden_layers, b, max_len,
                         cfg.num_key_value_heads,
                         cfg.hidden_size // cfg.num_attention_heads, cfg.dtype)

    def gather_beams(tree, beam_idx):
        """tree leaves [B*K, ...] reordered by beam_idx [B, K] (scalar leaves
        like the cache length pass through)."""
        def g(x):
            if jnp.ndim(x) == 0:
                return x
            xk = x.reshape((b, K) + x.shape[1:])
            idx = beam_idx.reshape((b, K) + (1,) * (x.ndim - 1))
            return jnp.take_along_axis(xk, idx, axis=1).reshape(x.shape)
        return jax.tree_util.tree_map(g, tree)

    @jax.jit
    def run(model, input_ids, cache):
        # prefill ONCE at batch B (beams are byte-identical pre-fork), then
        # tile the cache along a beam axis
        logits, cache = llama_forward_with_cache(model, input_ids, cache, 0)
        cache = jax.tree_util.tree_map(
            lambda x: x if jnp.ndim(x) == 0 else jnp.repeat(x, K, axis=0), cache)
        logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        logp = jnp.broadcast_to(logp[:, None, :], (b, K, V))

        # beam 0 starts live, the rest masked so step 0 picks K distinct tokens
        running_lp = jnp.tile(jnp.array([0.0] + [NEG] * (K - 1)), (b, 1))
        seqs = jnp.zeros((b, K, max_len), input_ids.dtype)
        seqs = seqs.at[:, :, :prompt_len].set(input_ids[:, None, :])
        fin_seqs = jnp.zeros_like(seqs)
        fin_scores = jnp.full((b, K), NEG)

        def select(running_lp, seqs, fin_seqs, fin_scores, logp, i):
            return beam_select(running_lp, seqs, fin_seqs, fin_scores,
                               logp, i, prompt_len, eos_token_id,
                               length_penalty)

        def step(carry, i):
            running_lp, seqs, fin_seqs, fin_scores, cache, logp = carry
            running_lp, seqs, fin_seqs, fin_scores, new_beam, new_tok = select(
                running_lp, seqs, fin_seqs, fin_scores, logp, i)
            cache = gather_beams(cache, new_beam)
            cur = new_tok.reshape(b * K, 1)
            logits, cache = llama_forward_with_cache(
                model, cur, cache, prompt_len + i)
            logp = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1).reshape(b, K, V)
            return (running_lp, seqs, fin_seqs, fin_scores, cache, logp), None

        carry = (running_lp, seqs, fin_seqs, fin_scores, cache, logp)
        (running_lp, seqs, fin_seqs, fin_scores, _, logp), _ = lax.scan(
            step, carry, jnp.arange(max_new_tokens - 1))
        # last token: pure selection, no forward needed after it
        running_lp, seqs, fin_seqs, fin_scores, _, _ = select(
            running_lp, seqs, fin_seqs, fin_scores, logp, max_new_tokens - 1)

        # merge still-running beams (at full length) with the finished pool
        run_score = running_lp / (float(max_new_tokens) ** length_penalty)
        all_scores = jnp.concatenate([fin_scores, run_score], axis=1)
        all_seqs = jnp.concatenate([fin_seqs, seqs], axis=1)
        best = jnp.argmax(all_scores, axis=1)
        best_seqs = jnp.take_along_axis(
            all_seqs, best[:, None, None], axis=1)[:, 0]
        best_scores = jnp.take_along_axis(all_scores, best[:, None], axis=1)[:, 0]
        if eos_token_id is not None:
            # early-finished hypotheses carry 0s after eos — pad with eos
            # (generate()'s convention)
            gen = best_seqs[:, prompt_len:]
            seen = jnp.cumsum(gen == eos_token_id, axis=1)
            after = jnp.concatenate(
                [jnp.zeros((b, 1), bool), (seen > 0)[:, :-1]], axis=1)
            best_seqs = best_seqs.at[:, prompt_len:].set(
                jnp.where(after, eos_token_id, gen))
        return best_seqs, best_scores

    return run(model, input_ids, cache)


def generic_generate(model, input_ids, max_new_tokens=32, temperature=0.0,
                     top_k=None, top_p=None, eos_token_id=None, rng=None,
                     repetition_penalty=1.0, min_new_tokens=0):
    """Family-agnostic decoding (ref PaddleNLP GenerationMixin over every
    causal architecture): works with ANY causal LM whose
    ``__call__(ids [B, S]) -> logits [B, S, V]`` — BLOOM, Falcon,
    GPT-J/NeoX, OPT, Gemma, Qwen2-MoE, custom models — with the same
    sampling/penalty/EOS semantics as ``generate``.

    The whole buffer is re-forwarded each step (no KV cache): position
    ``p``'s logits depend only on tokens ``<= p`` under causal masking,
    so the zero-padded future is inert. O(S^2) attention per token —
    the correctness-first generic path; the LLaMA family's ``generate``
    is the cached fast path. One jitted while_loop, fixed shapes.
    """
    cfg = model.cfg
    b, prompt_len = input_ids.shape
    max_len = prompt_len + max_new_tokens
    vocab = cfg.vocab_size
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def constrain(logits, appeared, gen_len):
        logits = _apply_repetition_penalty(logits, appeared,
                                           repetition_penalty)
        if eos_token_id is not None and min_new_tokens > 0:
            logits = jnp.where(
                (gen_len < min_new_tokens)
                & (jnp.arange(logits.shape[-1]) == eos_token_id)[None, :],
                -1e30, logits)
        return logits

    @jax.jit
    def run(model, input_ids, rng):
        tokens = jnp.concatenate(
            [input_ids, jnp.zeros((b, max_new_tokens), input_ids.dtype)],
            axis=1)
        appeared = jnp.zeros((b, vocab), bool)
        appeared = appeared.at[jnp.arange(b)[:, None], input_ids].set(True)

        def logits_at(tokens, pos):
            lg = model(tokens).astype(jnp.float32)
            return lax.dynamic_index_in_dim(lg, pos, 1, keepdims=False)

        logits = constrain(logits_at(tokens, prompt_len - 1), appeared, 0)
        next_tok = _sample(logits, rng, temperature, top_k, top_p)
        appeared = appeared.at[jnp.arange(b), next_tok].set(True)
        tokens = tokens.at[:, prompt_len].set(next_tok)
        done = (jnp.zeros((b,), bool) if eos_token_id is None
                else (next_tok == eos_token_id))

        def cond(state):
            i, tokens, rng, done, appeared = state
            return jnp.logical_and(i < max_new_tokens - 1, ~jnp.all(done))

        def body(state):
            i, tokens, rng, done, appeared = state
            rng, sub = jax.random.split(rng)
            logits = constrain(logits_at(tokens, prompt_len + i), appeared,
                               i + 1)
            nxt = _sample(logits, sub, temperature, top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            appeared = appeared.at[jnp.arange(b), nxt].set(True)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, None], prompt_len + i + 1, axis=1)
            return (i + 1, tokens, rng, done, appeared)

        state = (jnp.zeros((), jnp.int32), tokens, rng, done, appeared)
        state = lax.while_loop(cond, body, state)
        return state[1]

    return run(model, jnp.asarray(input_ids), rng)


def generic_seq2seq_generate(model, encoder_inputs, max_new_tokens=20,
                             decoder_start_token_id=0, eos_token_id=None,
                             attention_mask=None, temperature=0.0,
                             top_k=None, top_p=None, rng=None):
    """Greedy decode for ANY encoder-decoder whose
    ``__call__(encoder_inputs, decoder_input_ids[, attention_mask])``
    returns [B, L, vocab] logits — BART/mBART/Pegasus, Whisper, custom
    (T5 ships its own encode-once ``generate``). Full re-forward per
    step (causal decoder masking makes the zero-padded future inert);
    one jitted fori_loop, fixed shapes. Returns [B, max_new_tokens]
    (EOS-filled after a row finishes)."""
    b = encoder_inputs.shape[0]
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    @jax.jit
    def run(model, encoder_inputs, attention_mask, rng):
        tokens = jnp.full((b, max_new_tokens + 1), decoder_start_token_id,
                          jnp.int32)

        def fwd(dec):
            if attention_mask is not None:
                return model(encoder_inputs, dec, attention_mask)
            return model(encoder_inputs, dec)

        def body(i, state):
            tokens, done, rng = state
            rng, sub = jax.random.split(rng)
            logits = fwd(tokens).astype(jnp.float32)
            step = lax.dynamic_index_in_dim(logits, i, 1, keepdims=False)
            nxt = _sample(step, sub, temperature, top_k,
                          top_p).astype(jnp.int32)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            tokens = tokens.at[:, i + 1].set(nxt)
            return tokens, done, rng

        done = jnp.zeros((b,), bool)
        tokens, _, _ = lax.fori_loop(0, max_new_tokens, body,
                                     (tokens, done, rng))
        return tokens[:, 1:]

    return run(model, jnp.asarray(encoder_inputs), attention_mask, rng)


def generic_seq2seq_beam_search(model, encoder_inputs, max_new_tokens=20,
                                num_beams=4, decoder_start_token_id=0,
                                eos_token_id=None, length_penalty=1.0,
                                attention_mask=None):
    """Beam search for ANY encoder-decoder ``__call__(enc, dec[, mask])``
    family — the same ``beam_select`` math as the causal-LM and paged
    beams, over full decoder re-forwards (beams ride a [B*K] leading dim;
    one batched forward per step). Returns
    (sequences [B, max_new_tokens], scores [B])."""
    enc = jnp.asarray(encoder_inputs)
    b = enc.shape[0]
    K = num_beams
    L = max_new_tokens + 1
    enc_t = jnp.repeat(enc, K, axis=0)
    mask_t = (None if attention_mask is None
              else jnp.repeat(jnp.asarray(attention_mask), K, axis=0))

    @jax.jit
    def run(model, enc_t, mask_t):
        NEG = jnp.float32(-1e9)
        seqs = jnp.full((b, K, L), decoder_start_token_id, jnp.int32)
        running_lp = jnp.broadcast_to(
            jnp.asarray([0.0] + [NEG] * (K - 1)), (b, K)).astype(jnp.float32)
        fin_seqs = jnp.zeros_like(seqs)
        fin_scores = jnp.full((b, K), NEG)

        def fwd(dec):
            if mask_t is not None:
                return model(enc_t, dec, mask_t)
            return model(enc_t, dec)

        def body(i, state):
            running_lp, seqs, fin_seqs, fin_scores = state
            logits = fwd(seqs.reshape(b * K, L)).astype(jnp.float32)
            step = lax.dynamic_index_in_dim(logits, i, 1, keepdims=False)
            logp = jax.nn.log_softmax(step, axis=-1).reshape(b, K, -1)
            running_lp, seqs, fin_seqs, fin_scores, _, _ = beam_select(
                running_lp, seqs, fin_seqs, fin_scores, logp, i, 1,
                eos_token_id, length_penalty)
            return running_lp, seqs, fin_seqs, fin_scores

        state = (running_lp, seqs, fin_seqs, fin_scores)
        running_lp, seqs, fin_seqs, fin_scores = lax.fori_loop(
            0, max_new_tokens, body, state)

        run_score = running_lp / (float(max_new_tokens) ** length_penalty)
        all_scores = jnp.concatenate([fin_scores, run_score], axis=1)
        all_seqs = jnp.concatenate([fin_seqs, seqs], axis=1)
        best = jnp.argmax(all_scores, axis=1)
        best_seq = jnp.take_along_axis(all_seqs, best[:, None, None],
                                       axis=1)[:, 0]
        best_score = jnp.take_along_axis(all_scores, best[:, None],
                                         axis=1)[:, 0]
        gen = best_seq[:, 1:]
        if eos_token_id is not None:
            seen = jnp.cumsum(gen == eos_token_id, axis=1)
            after = jnp.concatenate(
                [jnp.zeros((b, 1), bool), (seen > 0)[:, :-1]], axis=1)
            gen = jnp.where(after, eos_token_id, gen)
        return gen, best_score

    return run(model, enc_t, mask_t)
