"""The Pallas kernels of the main path compile for a TPU v5e that is
DESCRIBED, not attached (``interpret=False``, real widths): what Mosaic
refuses — a slice off the (8, 128) tiling, too much scoped VMEM — fails
here at no chip time. Interpret-mode tests cannot see either.

Rules this file keeps (``on-chip-measurement`` guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be, never at import; everything built from it is built in a fixture
or a test; no ``autouse``, no child process, one file. A compile that
passes is not a chip run: ``chip_smoke.py`` is.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.ops.pallas import latent_attention
from paddle_tpu.ops.pallas.norms import rms_norm
from paddle_tpu.ops.pallas import paged_attention
from paddle_tpu.ops.pallas.paged_attention import (
    decode_slab_is_tiled, paged_chunk_attention_pallas,
    paged_decode_attention, paged_decode_attention_pallas)
from paddle_tpu.ops.pallas.rope import fused_rope

bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; assert a Mosaic kernel is in
    the program. ``shapes`` are (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(n, bs, h_kv, d, dtype=bf16):
    return [((n, bs, h_kv, d), dtype)] * 2


# "%name = dtype[dims]{layout} opcode(" of an HLO instruction
_HLO_RESULT = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(")


def _assert_pool_read_in_place(compiled, pool_elems, but=()):
    """A kernel that reads the pool where it lies leaves no transposed or
    reshaped copy of it in the program, in HBM or anywhere else: no such
    instruction yields as many elements as a pool holds (``but``: sizes
    that are something else's, the folded queries')."""
    for line in compiled.as_text().splitlines():
        m = _HLO_RESULT.match(line)
        if m and m.group(2) in ("copy", "transpose", "reshape", "fusion"):
            elems = math.prod(map(int, m.group(1).split(",")))
            assert elems < pool_elems or elems in but, line.strip()[:160]

# (id, B, H, H_kv, head dim, pool blocks, block size, table width, pool
#  dtype, variant)
DECODE = [
    ("b8_h16", 8, 16, 16, 128, 512, 16, 32, bf16, {}),
    ("b8_gqa32_8", 8, 32, 8, 128, 512, 16, 32, bf16, {}),
    ("b8_h16_int8", 8, 16, 16, 128, 512, 16, 32, i8, {}),
    # chip_smoke.py's tick: 32 heads of 128, 2048 x 16 pool, 256-wide table
    ("smoke_b8_h32_table256", 8, 32, 32, 128, 2048, 16, 256, bf16, {}),
    ("b8_h32_table512", 8, 32, 32, 128, 4096, 16, 512, bf16, {}),
    # mistral7b.serve.backlog's tick: 16 slots, 3072 x 16 pool; its variants
    ("b16_gqa32_8_pool3072_table256", 16, 32, 8, 128, 3072, 16, 256, bf16,
     {}),
    ("cell_window", 16, 32, 8, 128, 3072, 16, 256, bf16, {"window": 1024}),
    ("cell_partials", 16, 32, 8, 128, 3072, 16, 256, bf16,
     {"partials": True}),
    ("cell_int8", 16, 32, 8, 128, 3072, 16, 256, i8, {}),
    # the edges of decode_slab_is_tiled: the fewest K/V heads a dtype's
    # sublane tile allows, a head of two lane rows, a head count that is
    # no power of two
    ("gqa16_2", 8, 16, 2, 128, 512, 16, 32, bf16, {}),
    ("gqa16_4_int8", 8, 16, 4, 128, 512, 16, 32, i8, {}),
    ("mqa_f32", 4, 8, 1, 128, 64, 16, 8, f32, {}),
    ("gqa16_2_d256", 8, 16, 2, 256, 512, 16, 32, bf16, {}),
    ("h40", 8, 40, 40, 128, 512, 16, 32, bf16, {}),
    # trinitymini.serve.mixedlen: 32 slots, 32 query heads to 4 K/V heads,
    # a table of 1,056 blocks; a window layer over the window space's pool
    # and the full layer over the full space's
    ("trinity_window_pool8256", 32, 32, 4, 128, 8256, 16, 1056, bf16,
     {"window": 2048}),
    ("trinity_full_pool33792", 32, 32, 4, 128, 33792, 16, 1056, bf16, {}),
]

# (id, H, H_kv, head dim, pool dtype): slabs off the pool's tiling, which
# the dispatcher sends to the gather
DECODE_UNTILED = [
    ("h16_d64", 16, 16, 64, bf16),
    ("gqa32_4_d64", 32, 4, 64, bf16),
    ("mqa_h8", 8, 1, 128, bf16),
    ("mqa_h71_d64", 71, 1, 64, bf16),
    ("mqa_h8_d256", 8, 1, 256, bf16),
    ("gqa16_2_int8", 16, 2, 128, i8),
    ("h12", 12, 12, 128, bf16),
]


def _decode_shapes(b, h, h_kv, d, n, bs, width, dtype):
    shapes = [((b, h, d), f32 if dtype == f32 else bf16),
              *_pool(n, bs, h_kv, d, dtype), ((b, width), i32), ((b,), i32)]
    if dtype == i8:
        shapes += [((n, bs, h_kv), f32)] * 2
    return shapes


@pytest.mark.parametrize("case", DECODE, ids=[c[0] for c in DECODE])
def test_paged_decode_attention_compiles(one_chip, case):
    _, b, h, h_kv, d, n, bs, width, dtype, variant = case
    assert decode_slab_is_tiled(h_kv, d, dtype)

    def fn(q, kp, vp, tables, lens, ks=None, vs=None):
        return paged_decode_attention_pallas(
            q, kp, vp, tables, lens, k_scale=ks, v_scale=vs,
            interpret=False, **variant)

    compiled = _compile(fn, one_chip,
                        *_decode_shapes(b, h, h_kv, d, n, bs, width, dtype))
    # no temporary in HBM of a pool's size (32 MB and up), nor elsewhere
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    _assert_pool_read_in_place(compiled, n * bs * h_kv * d)


@pytest.mark.parametrize("case", DECODE_UNTILED,
                         ids=[c[0] for c in DECODE_UNTILED])
def test_paged_decode_off_the_tiling_takes_the_gather(one_chip, case,
                                                      monkeypatch):
    """Mosaic refuses to copy these slabs (the rule is not wider than it
    has to be); the dispatcher, on a TPU, says so and compiles the XLA
    formulation for them."""
    _, h, h_kv, d, dtype = case
    assert not decode_slab_is_tiled(h_kv, d, dtype)
    shapes = _decode_shapes(8, h, h_kv, d, 512, 16, 32, dtype)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]

    def kernel(q, kp, vp, tables, lens, ks=None, vs=None):
        return paged_decode_attention_pallas(
            q, kp, vp, tables, lens, k_scale=ks, v_scale=vs, interpret=False)

    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(kernel).lower(*args).compile()

    def dispatched(q, kp, vp, tables, lens, ks=None, vs=None):
        return paged_decode_attention(q, kp, vp, tables, lens, k_scale=ks,
                                      v_scale=vs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    del paged_attention._trace_events[:]
    compiled = jax.jit(dispatched).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert {"decode:slab-off-tiling", "decode:xla"} <= set(
        paged_attention._trace_events)
    assert "decode:pallas" not in paged_attention._trace_events


# (id, rows A, chunk C, H, H_kv, pool blocks, table width, int8 pool)
CHUNK = [
    ("a2_c128_h16", 2, 128, 16, 16, 512, 32, False),
    ("verify_a8_c5_h16", 8, 5, 16, 16, 512, 32, False),
    ("a2_c128_gqa32_8", 2, 128, 32, 8, 512, 32, False),
    # chip_smoke.py's chunked prefill: 8 rows x 128-token chunk, 32 heads
    ("smoke_a8_c128_h32_table256", 8, 128, 32, 32, 2048, 256, False),
    ("a8_c128_h32_table512", 8, 128, 32, 32, 4096, 512, False),
    ("short_chunk_a2_c44_h32", 2, 44, 32, 32, 2048, 256, False),
    ("odd_chunk_a3_c7_gqa32_8", 3, 7, 32, 8, 512, 32, False),
    ("a2_c128_h32_int8", 2, 128, 32, 32, 2048, 256, True),
    ("verify_a8_c5_h32_int8", 8, 5, 32, 32, 2048, 256, True),
    # mistral7b.serve.backlog's chunk forward: 16 rows x 256-token chunk
    ("a16_c256_gqa32_8_table256_pool3072", 16, 256, 32, 8, 3072, 256, False),
    ("cell_int8", 16, 256, 32, 8, 3072, 256, True),
    # ouro2.6b.serve.reasoning's: 8 rows, a pool of 4 passes x 184 blocks
    ("ouro_a8_c256_h16_table48_pool736", 8, 256, 16, 16, 736, 48, False),
    # the fewest K/V heads a bf16 slab's tiling allows
    ("a4_c64_gqa16_2", 4, 64, 16, 2, 512, 32, False),
    # the two cells' chunk forwards as they are sent since ISSUE 31: one
    # row a call (``LLMEngine.prefill_rows`` at ``max_prompt_len`` 256)
    ("mistral_a1_c256_gqa32_8_table256_pool3072", 1, 256, 32, 8, 3072, 256,
     False),
    ("ouro_a1_c256_h16_table48_pool736", 1, 256, 16, 16, 736, 48, False),
    # variants of the Mistral cell's shape that no cell runs
    ("cell_window", 16, 256, 32, 8, 3072, 256, False, {"window": 1024}),
    ("cell_partials", 16, 256, 32, 8, 3072, 256, False, {"partials": True}),
    # trinitymini.serve.mixedlen's chunk forward: one row of 2,048 tokens,
    # 32 query heads to 4 K/V heads, in each of its two block spaces
    ("trinity_window_a1_c2048_gqa32_4_pool8256", 1, 2048, 32, 4, 8256, 1056,
     False, {"window": 2048}),
    ("trinity_full_a1_c2048_gqa32_4_pool33792", 1, 2048, 32, 4, 33792, 1056,
     False),
]


@pytest.mark.parametrize("case", CHUNK, ids=[c[0] for c in CHUNK])
def test_paged_chunk_attention_compiles(one_chip, case):
    _, a, c, h, h_kv, n, width, int8, *variant = case
    shapes = [((a, c, h, 128), bf16), *_pool(n, 16, h_kv, 128,
                                             i8 if int8 else bf16),
              ((a, width), i32), ((a,), i32), ((a,), i32)]
    if int8:
        shapes += [((n, 16, h_kv), f32)] * 2

    def fn(q, kp, vp, tables, offs, cls, ks=None, vs=None):
        return paged_chunk_attention_pallas(
            q, kp, vp, tables, offs, cls, k_scale=ks, v_scale=vs,
            interpret=False, **dict(*variant))

    _assert_pool_read_in_place(_compile(fn, one_chip, *shapes),
                               n * 16 * h_kv * 128, but=(a * c * h * 128,))


@pytest.mark.parametrize("case", DECODE_UNTILED[:3] + DECODE_UNTILED[5:6],
                         ids=[c[0] for c in DECODE_UNTILED[:3]
                              + DECODE_UNTILED[5:6]])
def test_paged_chunk_off_the_tiling_takes_the_gather(one_chip, case,
                                                     monkeypatch):
    """The chunk kernel copies the same slabs as the decode kernel: off
    ``decode_slab_is_tiled`` the dispatcher, on a TPU, says so and
    compiles the XLA formulation."""
    _, h, h_kv, d, dtype = case
    assert not decode_slab_is_tiled(h_kv, d, dtype)
    shapes = [((4, 32, h, d), bf16), *_pool(512, 16, h_kv, d, dtype),
              ((4, 32), i32), ((4,), i32), ((4,), i32)]
    if dtype == i8:
        shapes += [((512, 16, h_kv), f32)] * 2
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]

    def dispatched(q, kp, vp, tables, offs, cls, ks=None, vs=None):
        return paged_attention.paged_chunk_attention(
            q, kp, vp, tables, offs, cls, k_scale=ks, v_scale=vs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    del paged_attention._trace_events[:]
    compiled = jax.jit(dispatched).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert {"chunk:slab-off-tiling", "chunk:xla"} <= set(
        paged_attention._trace_events)
    assert "chunk:pallas" not in paged_attention._trace_events


@pytest.mark.parametrize("family", ["llama", "ouro"])
def test_chunk_program_lowers_the_kernel_once(one_chip, monkeypatch, family):
    """Every layer of a chunk program (a looped model's bodies too) calls
    the chunk kernel through one jitted function, so the lowered module
    holds ONE Mosaic custom call for it whatever the depth: the kernel is
    traced and lowered to Mosaic once a program (``setup_s``), while the
    dispatcher still leaves a breadcrumb a call site. Lowered for a TPU,
    not compiled: a count of what the lowering holds."""
    from paddle_tpu.models import paged
    if family == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(
            num_hidden_layers=4, hidden_size=256, num_attention_heads=2,
            num_key_value_heads=2, intermediate_size=512, vocab_size=128,
            dtype=bf16)
        model = jax.eval_shape(lambda: LlamaForCausalLM(cfg))
    else:
        from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
        cfg = OuroConfig.tiny(
            num_hidden_layers=3, hidden_size=256, num_attention_heads=2,
            num_key_value_heads=2, intermediate_size=512, vocab_size=128,
            total_ut_steps=4, dtype=bf16)
        model = jax.eval_shape(lambda: OuroForCausalLM(cfg))
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init_for(
        cfg, 32, 16, 4, 8))
    S = jax.ShapeDtypeStruct
    args = (model, S((4, 32), i32), S((4,), i32), S((4,), i32), cache,
            S((4,), i32), S((4, 8), i32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged.clear_jit_caches()
    del paged_attention._trace_events[:]
    try:
        text = jax.jit(paged.llama_prefill_chunk_paged).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        paged.clear_jit_caches()     # traced under a patched backend
    kernels = re.findall(r'kernel_name = "([^"]+)"', text)
    assert kernels.count("paged_chunk_attention") == 1, kernels
    sites = len(re.findall(r"call @_paged_chunk_call", text))
    assert sites == cfg.num_hidden_layers
    assert paged_attention._trace_events.count("chunk:pallas") == sites


# (id, rows, tokens, heads, d_k, d_v): Olmo-Hybrid-7B's linear layers at the
# cell's chunk of 1024 tokens and a short ragged call
DELTA_CHUNK = [("olmo7b_1x1024", 1, 1024, 30, 96, 192),
               ("olmo7b_2x200", 2, 200, 30, 96, 192)]


@pytest.mark.parametrize("case", DELTA_CHUNK, ids=lambda c: c[0])
def test_gated_delta_chunk_compiles(one_chip, case):
    from paddle_tpu.ops.pallas import gated_delta
    _, b, t, h, dk, dv = case
    fn = lambda *a: gated_delta.gated_delta_chunk_pallas(*a, interpret=False)
    compiled = _compile(
        fn, one_chip, ((b, t, h, dk), bf16), ((b, t, h, dk), bf16),
        ((b, t, h, dv), bf16), ((b, t, h), f32), ((b, t, h), f32),
        ((b, h, dk, dv), f32), ((b,), i32))
    assert 'kernel_name = "gated_delta_chunk"' in compiled.as_text() \
        or "gated_delta_chunk" in compiled.as_text()


def test_gated_delta_step_updates_the_states_where_they_lie(one_chip):
    """The step is XLA's own form (no kernel): compiled for the chip at the
    cell's shape with the state donated, it holds no second copy of the
    slots' states."""
    from paddle_tpu.ops.pallas import gated_delta
    n, h, dk, dv = 8, 30, 96, 192
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n, h, dk), bf16), ((n, h, dk), bf16), ((n, h, dv), bf16),
        ((n, h), f32), ((n, h), f32), ((n, h, dk, dv), f32),
        ((n,), jnp.bool_))]
    compiled = jax.jit(gated_delta.gated_delta_step,
                       donate_argnums=(5,)).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    state = n * h * dk * dv * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state // 2


def test_hybrid_chunk_program_lowers_each_kernel_once(monkeypatch):
    """A period of the hybrid: three linear layers and a full one call the
    delta-rule kernel and the chunk-attention kernel through one jitted
    function each, so the lowered chunk program holds one Mosaic call of
    either; the tick program's only kernel is the decode attention (the
    rule's step is XLA's own form)."""
    from paddle_tpu.models import paged
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    from paddle_tpu.ops.pallas import gated_delta
    cfg = OlmoHybridConfig.tiny(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=2,
        intermediate_size=512, vocab_size=128, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=96,
        linear_value_head_dim=192, dtype=bf16)
    model = jax.eval_shape(lambda: OlmoHybridForCausalLM(cfg))
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init_for(
        cfg, 32, 16, 4, 8))
    S = jax.ShapeDtypeStruct
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged.clear_jit_caches()
    del paged_attention._trace_events[:]
    try:
        chunk = jax.jit(paged.llama_prefill_chunk_paged).trace(
            model, S((1, 128), i32), S((1,), i32), S((1,), i32), cache,
            S((1,), i32), S((1, 8), i32)).lower(
                lowering_platforms=("tpu",)).as_text()
        tick = jax.jit(paged.llama_decode_step_paged).trace(
            model, S((4,), i32), cache, S((4,), jnp.bool_)).lower(
                lowering_platforms=("tpu",)).as_text()
    finally:
        paged.clear_jit_caches()     # traced under a patched backend
    kernels = re.findall(r'kernel_name = "([^"]+)"', chunk)
    assert sorted(kernels) == ["gated_delta_chunk", "paged_chunk_attention"]
    assert len(re.findall(r"call @_gated_delta_chunk_call", chunk)) == 3
    kernels = re.findall(r'kernel_name = "([^"]+)"', tick)
    assert set(kernels) == {"paged_decode_attention"}
    assert paged_attention._trace_events.count("gated_delta_chunk:pallas") \
        == 3


# ---------------------------------------------------------------------------
# The decode tick whole, sampler included (ISSUE 38): compiled for the chip,
# the sampler's full-vocabulary sort (with its softmax, cumulative sum and
# draw) stays under ONE conditional, in the branch a greedy tick does not
# take. A compiler that flattened the conditional into a select, or hoisted
# the sort out of it, would put the sort back in every tick.
def _computations(hlo):
    """An HLO module's text -> {computation name: its body}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = "ENTRY" if line.startswith("ENTRY") else m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


def _reached(comps, root):
    """The computations ``root`` calls, however deep, and itself."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [n for n in re.findall(r"%([\w.\-]+)", comps[c])
                     if n in comps]
    return seen


def _assert_sort_under_the_samplers_conditional(hlo):
    comps = _computations(hlo)
    conds = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}"
                       r".*/sampler/cond", hlo)
    assert len(conds) == 1, conds
    greedy, stochastic = (_reached(comps, b.strip().lstrip("%"))
                          for b in conds[0].split(","))
    sorts = {c for c, body in comps.items() if " sort(" in body}
    assert sorts and sorts <= stochastic - greedy, sorts


def _assert_no_loop_outside_the_sampler(hlo):
    """Every ``while`` of the program lies under the sampler's
    conditional (its stochastic branch's draw): none in a layer's body."""
    comps = _computations(hlo)
    conds = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}"
                       r".*/sampler/cond", hlo)
    assert len(conds) == 1, conds
    under = set().union(*(_reached(comps, b.strip().lstrip("%"))
                          for b in conds[0].split(",")))
    loops = {c for c, body in comps.items() if " while(" in body}
    assert loops <= under, loops - under


def _tiny_served(family):
    """(abstract model, abstract cache of 8 slots) of a served family on
    the slab tiling."""
    from paddle_tpu.models import paged
    kw = dict(hidden_size=256, num_attention_heads=2, num_key_value_heads=2,
              intermediate_size=512, vocab_size=4096, dtype=bf16)
    if family == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg, cls = LlamaConfig.tiny(num_hidden_layers=2, **kw), \
            LlamaForCausalLM
    elif family == "ouro":
        from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
        cfg, cls = OuroConfig.tiny(num_hidden_layers=2, total_ut_steps=4,
                                   **kw), OuroForCausalLM
    else:
        from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                   OlmoHybridForCausalLM)
        cfg, cls = OlmoHybridConfig.tiny(
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=96, linear_value_head_dim=192, **kw), \
            OlmoHybridForCausalLM
    return (jax.eval_shape(lambda: cls(cfg)),
            jax.eval_shape(lambda: paged.PagedKVCache.init_for(
                cfg, 32, 16, 8, 8)))


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _tick_args(model, cache, sharding):
    """``llama_decode_tick``'s array arguments for 8 slots, as shapes, every
    leaf with ``sharding``."""
    S = jax.ShapeDtypeStruct
    return _placed((model, S((8,), i32), cache, S((8,), jnp.bool_),
                    S((8,), i32), S((8,), i32), S((8,), i32),
                    S((2,), jnp.uint32), S((8,), f32), S((8,), f32)),
                   sharding)


def _program_for_the_chip(monkeypatch, jitted, *args):
    """The program, compiled with the dispatchers on their TPU side."""
    from paddle_tpu.models import paged
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged.clear_jit_caches()
    try:
        return jitted.lower(*args).compile()
    finally:
        paged.clear_jit_caches()     # traced under a patched backend


def _compiled_for_the_chip(monkeypatch, jitted, *args):
    """That program's text."""
    return _program_for_the_chip(monkeypatch, jitted, *args).as_text()


@pytest.mark.parametrize("family", ["llama", "ouro", "hybrid"])
def test_decode_tick_holds_its_sort_under_a_conditional(one_chip,
                                                        monkeypatch, family):
    from paddle_tpu.models import paged
    tick = jax.jit(paged.llama_decode_tick, static_argnums=(10, 11),
                   donate_argnums=(2,))
    text = _compiled_for_the_chip(
        monkeypatch, tick, *_tick_args(*_tiny_served(family), one_chip),
        None, False)
    assert "paged_decode_attention" in text
    _assert_sort_under_the_samplers_conditional(text)


@pytest.mark.parametrize("family", ["llama", "ouro", "hybrid"])
def test_staged_tick_is_the_same_program_for_the_chip(one_chip, monkeypatch,
                                                      family):
    """The tick as the executor sends it, its seven host arrays one int32
    vector taken apart by static slices: the kernel and the sampler's
    conditional as in the body alone, and no more than the vector, the
    key and the cache's leaves come in."""
    from paddle_tpu.models import paged
    model, cache = _tiny_served(family)
    layout = paged.tick_staging(8)
    S = jax.ShapeDtypeStruct
    args = _placed((model, S((layout.size,), i32), cache,
                    S((2,), jnp.uint32)), one_chip)
    text = _compiled_for_the_chip(monkeypatch, paged._TICK_JIT, *args,
                                  layout, None, False)
    assert "paged_decode_attention" in text
    _assert_sort_under_the_samplers_conditional(text)
    assert f"s32[{layout.size}]" in text


def test_cp_decode_tick_holds_its_sort_under_a_conditional(v5e_2x2,
                                                           monkeypatch):
    """The cp tick as the executor builds it: the tick under a ``shard_map``
    over the four chips, pools sharded on their blocks, everything else
    (the temperatures, so the predicate) replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.models import paged
    model, cache = _tiny_served("llama")
    mesh = Mesh(np.array(v5e_2x2), ("cp",))
    R, pool = P(), P("cp")
    cs = paged.PagedKVCache(pool, pool, R, R, pool, pool)

    def tick(model, tokens, cache, active, rows, cols, vals, rng, temps,
             top_ps):
        return paged.llama_decode_tick(
            model, tokens, cache, active, rows, cols, vals, rng, temps,
            top_ps, None, False, None, None, cp_axis="cp")

    fn = jax.jit(jax.shard_map(
        tick, mesh=mesh, check_vma=False,
        in_specs=(R, R, cs, R, R, R, R, R, R, R), out_specs=(R, R, cs)),
        donate_argnums=(2,))
    args = list(_tick_args(model, cache, NamedSharding(mesh, R)))
    sharded = NamedSharding(mesh, pool)
    args[2] = paged.PagedKVCache(
        _placed(cache.k_pools, sharded), _placed(cache.v_pools, sharded),
        args[2].block_tables, args[2].lens,
        _placed(cache.k_scales, sharded), _placed(cache.v_scales, sharded),
        cache.passes)
    _assert_sort_under_the_samplers_conditional(
        _compiled_for_the_chip(monkeypatch, fn, *args))


# (id, B, S, H, H_kv, window, with backward)
FLASH = [
    ("fwd_4x2048x16", 4, 2048, 16, 16, None, False),
    ("fwd_bwd_4x2048x16", 4, 2048, 16, 16, None, True),
    ("fwd_bwd_window1024_gqa32_8", 1, 4096, 32, 8, 1024, True),
    # chip_smoke.py's train step and padded prefill: 32 heads of 128
    ("smoke_fwd_bwd_2x2048x32", 2, 2048, 32, 32, None, True),
]


@pytest.mark.parametrize("case", FLASH, ids=[c[0] for c in FLASH])
def test_flash_attention_compiles(one_chip, case):
    _, b, s, h, h_kv, window, bwd = case
    attend = functools.partial(flash_attention, causal=True, window=window,
                               interpret=False)
    fn = attend
    if bwd:
        fn = jax.grad(lambda q, k, v: attend(q, k, v).astype(f32).sum(),
                      argnums=(0, 1, 2))
    _compile(fn, one_chip, ((b, s, h, 128), bf16),
             *[((b, s, h_kv, 128), bf16)] * 2)


# (id, B, S, H, H_kv): the padded-varlen path of the engine's whole-prompt
# prefill; the cells' since ISSUE 31 are one row of 256 a call
FLASH_KV_LENS = [("smoke_8x128x32", 8, 128, 32, 32),
                 ("mistral_cell_1x256_gqa32_8", 1, 256, 32, 8),
                 ("ouro_cell_1x256x16", 1, 256, 16, 16)]


@pytest.mark.parametrize("case", FLASH_KV_LENS,
                         ids=[c[0] for c in FLASH_KV_LENS])
def test_flash_attention_kv_lens_compiles(one_chip, case):
    _, b, s, h, h_kv = case
    fn = lambda q, k, v, lens: flash_attention(
        q, k, v, causal=True, kv_lens=lens, interpret=False)
    _compile(fn, one_chip, ((b, s, h, 128), bf16),
             *[((b, s, h_kv, 128), bf16)] * 2, ((b,), i32))


# (id, rows, experts, k, n, with backward)
GROUPED = [
    ("e8_2048x5504_fwd_bwd", 4096, 8, 2048, 5504, True),
    ("e64_2048x1024_256rows", 256, 64, 2048, 1024, False),
    # kimik2.serve.longshared: 12 held experts of 7168 x 2048, a 32-slot
    # tick's 256 pairs and a 2,048-token chunk's common branch
    ("kimi_gate_up_tick", 256, 12, 7168, 4096, False),
    ("kimi_down_tick", 256, 12, 2048, 7168, False),
    ("kimi_gate_up_chunk", 2048, 12, 7168, 4096, False),
    ("kimi_down_chunk", 2048, 12, 2048, 7168, False),
    ("kimi_down_chunk_all_pairs", 16384, 12, 2048, 7168, False),
    # trinitymini.serve.mixedlen: all 128 experts of 2048 x 1024 held; a
    # 32-slot tick's 256 pairs (two rows an expert) and a 2,048-token
    # chunk's 16,384
    ("trinity_gate_up_tick_128_groups", 256, 128, 2048, 2048, False),
    ("trinity_down_tick_128_groups", 256, 128, 1024, 2048, False),
    ("trinity_gate_up_chunk_128_groups", 16384, 128, 2048, 2048, False),
    ("trinity_down_chunk_128_groups", 16384, 128, 1024, 2048, False),
]


@pytest.mark.parametrize("case", GROUPED, ids=[c[0] for c in GROUPED])
def test_grouped_matmul_compiles(one_chip, case):
    _, m, e, k, n, bwd = case
    gmm = functools.partial(grouped_matmul, impl="pallas", interpret=False)
    fn = gmm
    if bwd:
        fn = jax.grad(lambda x, w, g: gmm(x, w, g).astype(f32).sum(),
                      argnums=(0, 1))
    text = _compile(fn, one_chip, ((m, k), bf16), ((e, k, n), bf16),
                    ((e,), i32)).as_text()
    # the kernel under the name the trace's readers match, inside the
    # compiler's own scoped VMEM whatever tiles the shape chose, and the
    # tile map beside it without a loop for the device
    # (a sum's gradient needs no forward product: the backward's two,
    # which autodiff's scopes wrap)
    if bwd:
        assert "grouped_matmul_dx" in text and "grouped_matmul_dw" in text
    else:
        assert re.search(r"%grouped_matmul(\.\d+)? = ", text)
    assert '"scoped_memory_configs":[{' not in text
    assert " while(" not in text


# ---- latent attention (MLA) at Kimi-K2's sizes: 64 heads over rows of
# 512 + 64 -> 640 values, the first 512 the value; the cell's pool
# (id, pool blocks, table width)
LATENT = [("cell_pool20480_table1056", 20480, 1056), ("table32", 512, 32)]


@pytest.mark.parametrize("case", LATENT, ids=[c[0] for c in LATENT])
def test_latent_decode_attention_compiles(one_chip, case):
    _, n, width = case
    fn = functools.partial(
        latent_attention.paged_latent_decode_attention_pallas, v_width=512,
        scale=0.13, interpret=False)
    compiled = _compile(fn, one_chip, ((32, 64, 640), bf16),
                        ((n, 16, 640), bf16), ((32, width), i32),
                        ((32,), i32))
    _assert_pool_read_in_place(compiled, n * 16 * 640)
    assert "paged_latent_decode_attention" in compiled.as_text()


@pytest.mark.parametrize("case", LATENT, ids=[c[0] for c in LATENT])
def test_latent_chunk_attention_compiles(one_chip, case):
    """The expanded chunk kernel at the cell's chunk: per-head queries,
    W_kvb as the model keeps it, the pool read where it lies, and no scoped
    VMEM asked beyond the compiler's own."""
    _, n, width = case
    fn = functools.partial(
        latent_attention.paged_latent_chunk_attention_pallas, scale=0.13,
        interpret=False)
    compiled = _compile(fn, one_chip, ((1, 2048, 64, 128), bf16),
                        ((1, 2048, 64, 64), bf16), ((512, 64, 256), bf16),
                        ((n, 16, 640), bf16), ((1, width), i32), ((1,), i32),
                        ((1,), i32))
    _assert_pool_read_in_place(       # q_rope, q_nope and the output, q
        compiled, n * 16 * 640, but=[2048 * 64 * d for d in (64, 128, 256)])
    text = compiled.as_text()
    assert "paged_latent_chunk_attention" in text
    assert '"scoped_memory_configs":[{' not in text


def test_latent_pool_off_the_tiling_takes_the_gather(one_chip, monkeypatch):
    """A block of 8 bf16 rows is half a sublane tile: the dispatcher sends
    it to the gather, and the program holds no Mosaic call."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = functools.partial(latent_attention.paged_latent_decode_attention,
                           v_width=512, scale=0.13)
    args = [jax.ShapeDtypeStruct(s_, d, sharding=one_chip) for s_, d in (
        ((8, 64, 640), bf16), ((64, 8, 640), bf16), ((8, 16), i32),
        ((8,), i32))]
    assert "tpu_custom_call" not in jax.jit(fn).lower(*args).compile() \
        .as_text()


def _kimi_served():
    """Kimi-K2's head sizes and ranks at a narrow hidden size, 12 of 384
    experts held: the programs' shapes, with leaves instead of weights."""
    from paddle_tpu.models import paged
    from paddle_tpu.models.kimi_k2 import KimiK2Config, KimiK2ForCausalLM
    cfg = KimiK2Config(
        vocab_size=4096, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256, num_hidden_layers=2,
        num_attention_heads=64, num_key_value_heads=64,
        held_experts=tuple(range(12)), dtype=bf16)
    return (jax.eval_shape(lambda: KimiK2ForCausalLM(cfg)),
            jax.eval_shape(lambda: paged.PagedKVCache.init_for(
                cfg, 256, 16, 8, 64)))


def test_kimi_tick_and_chunk_programs_compile_with_their_kernels(
        one_chip, monkeypatch):
    """The staged tick and chunk programs of a latent model with held
    experts: the latent kernels and the grouped products in them, the
    tick's two counts behind its tokens, the chunk's beside its cache."""
    from paddle_tpu.models import paged
    model, cache = _kimi_served()
    S = jax.ShapeDtypeStruct
    layout = paged.tick_staging(8)
    args = _placed((model, S((layout.size,), i32), cache,
                    S((2,), jnp.uint32)), one_chip)
    text = _compiled_for_the_chip(monkeypatch, paged._TICK_JIT, *args,
                                  layout, None, False)
    assert "paged_latent_decode_attention" in text
    assert "grouped_matmul" in text and "s32[10]" in text
    assert '"scoped_memory_configs":[{' not in text
    layout = paged.prefill_staging(1, 2048, 64, True)
    args = _placed((model, S((layout.size,), i32), cache), one_chip)
    chunk = _program_for_the_chip(monkeypatch, paged._PREFILL_CHUNK_JIT,
                                  *args, layout)
    text = chunk.as_text()
    assert "paged_latent_chunk_attention" in text
    # the expanded form keeps no K or V in HBM and forms no absorbed
    # query: this program's temporaries are 68.6 MiB by the compiler's
    # count, where the absorbed form's q~ [2048, 64, 640] and latent
    # outputs [2048, 64, 512] made them 345.9 (PERF.md section 6, PR 42)
    assert chunk.memory_analysis().temp_size_in_bytes < 96 << 20
    assert "grouped_matmul" in text and "while" in text
    # no kernel of the program asks for another scoped-VMEM size than the
    # compiler's: one that does makes XLA give every operation a region of
    # its own, and with two conditionals beside the latent chunk kernel's
    # 48 MiB the chip never returned (PERF.md section 6, PR 41)
    assert '"scoped_memory_configs":[{' not in text


def test_two_space_tick_and_chunk_programs_compile_with_their_kernels(
        one_chip, monkeypatch):
    """The staged tick and chunk programs of a model with window layers
    beside full ones and every expert held (Trinity's head sizes at a
    narrow hidden size): the paged kernels over both block spaces, the
    window table's staging behind the tick's seven arrays, the grouped
    products, the counts a K/V layer's expert block hands out."""
    from paddle_tpu.models import paged
    from paddle_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
    cfg = TrinityConfig(
        vocab_size=8192, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256, num_hidden_layers=5, num_dense_layers=1,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention", "sliding_attention",
                     "sliding_attention"), num_experts=16, dtype=bf16)
    model = jax.eval_shape(lambda: TrinityForCausalLM(cfg))
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init_for(
        cfg, 512, 16, 8, 160, window_blocks=264))
    S = jax.ShapeDtypeStruct
    layout = paged.tick_staging(8, True)
    assert layout.size == 8 * 8
    args = _placed((model, S((layout.size,), i32), cache,
                    S((2,), jnp.uint32)), one_chip)
    text = _compiled_for_the_chip(monkeypatch, paged._TICK_JIT, *args,
                                  layout, None, False)
    assert text.count("paged_decode_attention") >= 5
    assert "grouped_matmul" in text and "s32[10]" in text
    assert "attention.window" in text and "attention.full" in text
    # the grouped products' tile map is compares and sums: the tick holds
    # no loop but what the sampler's stochastic branch may draw with
    _assert_no_loop_outside_the_sampler(text)
    assert '"scoped_memory_configs":[{' not in text
    layout = paged.prefill_staging(1, 2048, 160, True, True)
    args = _placed((model, S((layout.size,), i32), cache), one_chip)
    chunk = _program_for_the_chip(monkeypatch, paged._PREFILL_CHUNK_JIT,
                                  *args, layout)
    text = chunk.as_text()
    assert "paged_chunk_attention" in text and "grouped_matmul" in text
    assert "attention.window" in text and "attention.full" in text
    # the head runs over the row's last position alone: no [2048, vocab]
    assert "2048,8192]" not in text.replace(" ", "")
    assert chunk.memory_analysis().temp_size_in_bytes < 96 << 20


# rows x hidden: chip_smoke.py's own (decode tick, chunk rows, reference
# forward, train step), then row counts that are no multiple of the
# 256-row tile and a hidden wide enough to shrink it
RMS = [(8192, 2048), (8, 4096), (1024, 4096), (80, 4096), (4096, 4096),
       (5, 4096), (300, 4096), (8188, 4096), (8192, 16384)]


@pytest.mark.parametrize("rows,hidden", RMS)
def test_rms_norm_compiles(one_chip, rows, hidden):
    fn = lambda x, w: rms_norm(x, w, 1e-5, False)
    _compile(fn, one_chip, ((rows, hidden), bf16), ((hidden,), bf16))


def test_fused_rope_compiles(one_chip):
    fn = lambda x, c, s: fused_rope(x, c, s, interpret=False)
    _compile(fn, one_chip, ((4, 2048, 16, 128), bf16),
             *[((2048, 64), f32)] * 2)


# ---- Mosaic kernels inside fully-manual shard_map bodies, four chips ----
# On a TPU the dispatchers take their Pallas kernels inside a shard_map
# whose axes are all manual. CPU tests never take that branch, so the
# backend check is patched here (in the test only) and one step of each
# such path is compiled for the described 2x2.
def _abstract(tree, mesh):
    return _placed(tree, mesh.replicated())


def _kernels_in(jitted, *args):
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def test_expert_parallel_step_compiles_with_grouped_kernel(
        v5e_2x2, monkeypatch):
    import paddle_tpu as pt
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.distributed.moe import MoELayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = HybridMesh(ep=4, devices=v5e_2x2)

    def loss(m, v):
        y, aux = m(v)
        return jnp.mean(y.astype(f32) ** 2) + 0.01 * aux

    with mesh:
        moe = _abstract(jax.eval_shape(lambda: MoELayer(
            hidden=512, intermediate=1024, num_experts=8, k=2,
            dtype=bf16)), mesh)
        x = jax.ShapeDtypeStruct((8, 256, 512), bf16,
                                 sharding=mesh.batch_sharding())
        assert _kernels_in(jax.jit(pt.value_and_grad(loss)), moe, x) > 0


@pytest.mark.parametrize("pp,tp", [(4, 1), (2, 2)])
def test_pipeline_step_compiles_with_flash_and_rms(v5e_2x2, monkeypatch,
                                                   pp, tp):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         init_llama_pp_state,
                                         make_llama_pp_train_step)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = HybridMesh(pp=pp, tp=tp, devices=v5e_2x2)
    cfg = LlamaConfig.tiny(
        hidden_size=512, num_attention_heads=4, num_key_value_heads=4,
        intermediate_size=1024, num_hidden_layers=4, vocab_size=1024,
        max_position_embeddings=512, dtype=bf16)
    optimizer = opt.AdamW(learning_rate=1e-3)
    with mesh:
        model = jax.eval_shape(lambda: LlamaForCausalLM(cfg))
        params, ost = _abstract(jax.eval_shape(
            lambda: init_llama_pp_state(LlamaForCausalLM(cfg), optimizer,
                                        mesh)), mesh)
        step = make_llama_pp_train_step(model, mesh, optimizer,
                                        num_microbatches=2)
        ids = jax.ShapeDtypeStruct((4, 256), i32, sharding=mesh.replicated())
        assert _kernels_in(step, params, ost, ids, ids) > 0


def test_chunk_partials_compile_inside_a_cp_shard_map(v5e_2x2):
    """cp's chunked prefill: every shard scores the pool blocks it owns
    and the triples are merged across the axis. The kernel (under its own
    ``jit``) inside a manual region, compiled for the four chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(v5e_2x2), ("cp",))

    def body(q, kp, vp, tables, offs, cls):
        acc, m, l = paged_chunk_attention_pallas(
            q, kp, vp, tables, offs, cls, partials=True, interpret=False)
        w = jnp.exp(m - jax.lax.pmax(m, "cp"))
        num = jax.lax.psum(acc * w[..., None], "cp")
        den = jax.lax.psum(l * w, "cp")
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)

    pool = P("cp")
    fn = jax.shard_map(body, mesh=mesh, check_vma=False, out_specs=P(),
                       in_specs=(P(), pool, pool, P(), P(), P()))
    shapes = [((4, 128, 32, 128), bf16, P()),
              ((4 * 512, 16, 8, 128), bf16, pool),
              ((4 * 512, 16, 8, 128), bf16, pool),
              ((4, 128), i32, P()), ((4,), i32, P()), ((4,), i32, P())]
    args = [jax.ShapeDtypeStruct(s, t, sharding=NamedSharding(mesh, spec))
            for s, t, spec in shapes]
    assert _kernels_in(jax.jit(fn), *args) == 1


def test_ulysses_attention_compiles_with_flash(v5e_2x2, monkeypatch):
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.distributed.ulysses import make_ulysses_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = HybridMesh(sp=4, devices=v5e_2x2)
    with mesh:
        qkv = [jax.ShapeDtypeStruct(
            (1, 2048, 8, 128), bf16,
            sharding=mesh.sharding(None, "sp", None, None))] * 3
        grad = jax.grad(lambda q, k, v: make_ulysses_attention(mesh)(
            q, k, v).astype(f32).sum(), argnums=(0, 1, 2))
        assert _kernels_in(jax.jit(grad), *qkv) > 0
