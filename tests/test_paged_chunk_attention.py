"""Fused ragged chunk attention (ISSUE 11): interpret-Pallas vs XLA
gather parity over GQA/MHA, mid-block offsets, degenerate chunk_lens,
sliding windows, and OOB-sentinel table slots; a kernel that raises
surfaces from its dispatcher (no downgrade to the gather); the
dispatcher choosing from the backend and the shapes alone; and
engine-level greedy identity of the interpreted kernels against the
gather — incl. spec decode, chunked prefill, int8 K/V and
preempt-replay."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import clear_jit_caches
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS


@pytest.fixture(autouse=True)
def _fresh_jits():
    # the dispatchers' rule is read at trace time: tests that patch it
    # must not inherit (or leak) programs traced on the other side
    clear_jit_caches()
    yield
    clear_jit_caches()


# ------------------------------------------------------------ parity

def _ragged_case(rng, a, c, h, h_kv, d, bs, mb, n, offs, cls):
    """Pool with garbage everywhere, distinct permuted live blocks per
    row, sentinel (= n) padding on unused table slots."""
    q = jnp.asarray(rng.normal(size=(a, c, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n, bs, h_kv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, bs, h_kv, d)), jnp.float32)
    tables = np.full((a, mb), n, np.int32)
    offs = np.asarray(offs, np.int32)
    cls = np.asarray(cls, np.int32)
    for i in range(a):
        need = -(-int(offs[i] + cls[i]) // bs)
        tables[i, :need] = rng.choice(n, size=need, replace=False)
    return q, kp, vp, jnp.asarray(tables), offs, cls


def _assert_live_parity(out_p, out_x, cls, tol=2e-5):
    # dead rows diverge by design (kernel emits 0, the dense path a
    # uniform average over fully-masked logits) — compare live rows only
    for i, cl in enumerate(np.asarray(cls)):
        cl = int(cl)
        if cl == 0:
            assert np.allclose(np.asarray(out_p)[i], 0.0)
            continue
        err = np.abs(np.asarray(out_p)[i, :cl]
                     - np.asarray(out_x)[i, :cl]).max()
        assert err < tol, f"row {i}: {err}"


@pytest.mark.parametrize("h,h_kv", [(8, 2), (4, 4)])
def test_chunk_parity_ragged(h, h_kv):
    """GQA and MHA over mid-block offsets with chunk_lens 0 and 1."""
    rng = np.random.default_rng(0)
    case = _ragged_case(rng, 4, 6, h, h_kv, 16, 8, 6, 32,
                        offs=[0, 5, 13, 3], cls=[6, 1, 0, 4])
    q, kp, vp, tables, offs, cls = case
    out_p = pa.paged_chunk_attention_pallas(q, kp, vp, tables, offs, cls,
                                            interpret=True)
    out_x = pa.paged_chunk_attention_xla(q, kp, vp, tables, offs, cls)
    _assert_live_parity(out_p, out_x, cls)


def test_chunk_parity_sliding_window():
    rng = np.random.default_rng(1)
    q, kp, vp, tables, offs, cls = _ragged_case(
        rng, 3, 7, 8, 4, 16, 8, 8, 40, offs=[20, 0, 37], cls=[7, 7, 5])
    out_p = pa.paged_chunk_attention_pallas(q, kp, vp, tables, offs, cls,
                                            window=10, interpret=True)
    out_x = pa.paged_chunk_attention_xla(q, kp, vp, tables, offs, cls,
                                         window=10)
    _assert_live_parity(out_p, out_x, cls)


def test_chunk_parity_multi_tile_with_padding():
    """cg = 13*3 = 39 folded rows at q_tile=16 → a 3-tile grid with 9
    padding rows in the last tile."""
    rng = np.random.default_rng(2)
    q, kp, vp, tables, offs, cls = _ragged_case(
        rng, 2, 13, 6, 2, 16, 8, 9, 40, offs=[7, 22], cls=[13, 9])
    out_p = pa.paged_chunk_attention_pallas(q, kp, vp, tables, offs, cls,
                                            q_tile=16, interpret=True)
    out_x = pa.paged_chunk_attention_xla(q, kp, vp, tables, offs, cls)
    _assert_live_parity(out_p, out_x, cls)


def test_chunk_parity_verify_shape():
    """The spec-verify batch shape: C = k+1 queries appended at a deep
    offset, every row a different live length."""
    rng = np.random.default_rng(3)
    q, kp, vp, tables, offs, cls = _ragged_case(
        rng, 4, 5, 8, 2, 32, 8, 10, 48, offs=[17, 40, 0, 63],
        cls=[5, 5, 5, 5])
    out_p = pa.paged_chunk_attention_pallas(q, kp, vp, tables, offs, cls,
                                            interpret=True)
    out_x = pa.paged_chunk_attention_xla(q, kp, vp, tables, offs, cls)
    _assert_live_parity(out_p, out_x, cls)


# ------------------------------------------ the grid of shapes and rows
# One case a line: (heads, chunk C, rows, offsets, window, pool, partials).
# The lines cover every pair of values of any two axes, then the two
# cells' own combinations; head_dim 16 and a block of 16 keep the
# interpreter quick, and a buffer of four blocks makes every row with
# more than 64 positions walk several compute blocks.
HEADS = {"mha16": (16, 16), "gqa32_8": (32, 8), "gqa8_2": (8, 2)}
GRID = [
    ("gqa32_8", 256, "all_live", "table_end", 1024, "int8", False),
    ("gqa32_8", 256, "one_of_16", "zero", 1024, "int8", True),
    ("gqa32_8", 44, "ragged", "mid_block", None, "int8", True),
    ("gqa32_8", 5, "dead_between", "table_end", None, "int8", False),
    ("gqa32_8", 5, "ragged", "zero", 1024, "bf16", False),
    ("gqa8_2", 256, "all_live", "mid_block", None, "int8", False),
    ("gqa8_2", 256, "ragged", "table_end", 1024, "bf16", False),
    ("gqa8_2", 44, "dead_between", "mid_block", None, "bf16", False),
    ("gqa8_2", 44, "one_of_16", "zero", 1024, "int8", False),
    ("gqa8_2", 5, "all_live", "mid_block", 1024, "bf16", True),
    ("mha16", 256, "dead_between", "zero", 1024, "int8", True),
    ("mha16", 256, "ragged", "table_end", 1024, "int8", False),
    ("mha16", 44, "all_live", "zero", None, "bf16", False),
    ("mha16", 44, "one_of_16", "table_end", None, "bf16", True),
    ("mha16", 5, "one_of_16", "mid_block", None, "int8", False),
    # mistral7b.serve.backlog's and ouro2.6b.serve.reasoning's own
    ("gqa32_8", 256, "one_of_16", "mid_block", None, "bf16", False),
    ("mha16", 256, "dead_between", "mid_block", None, "bf16", False),
    ("gqa32_8", 256, "dead_between", "table_end", 1024, "bf16", True),
]
_BS, _WIDTH = 16, 84          # 1,344 positions a row: past a 1,024 window


def _grid_case(rng, heads, c, rows, offsets, pool, partials, poison=False):
    """-> (args, scales, live): q bf16, a pool of ``pool`` whose blocks no
    live table entry names hold NaN when ``poison`` (an int8 pool: NaN
    scales), tables padded with the sentinel, every third live entry of a
    row another shard's under ``partials``."""
    h, h_kv = HEADS[heads]
    d, bs, mb = 16, _BS, _WIDTH
    a = {"all_live": 3, "one_of_16": 16, "dead_between": 3, "ragged": 4}[rows]
    cls = {"all_live": [c] * 3, "one_of_16": [0] * 9 + [c] + [0] * 6,
           "dead_between": [c, 0, max(1, c - 3)],
           "ragged": [c, 1, max(1, c // 2), max(1, c - 1)]}[rows]
    room = mb * bs - c
    off = {"zero": 0, "mid_block": 5 * bs + 7, "table_end": room}[offsets]
    offs = [max(0, off - 3 * i) if n else 0 for i, n in enumerate(cls)]
    n = 16 + sum(-(-(o + l) // bs) for o, l in zip(offs, cls))
    q = jnp.asarray(rng.normal(size=(a, c, h, d)), jnp.bfloat16)
    kf = rng.normal(size=(2, n, bs, h_kv, d)).astype(np.float32)
    tables = np.full((a, mb), n, np.int32)
    free, at = rng.permutation(n), 0
    for i in range(a):
        need = -(-(offs[i] + cls[i]) // bs) if cls[i] else 0
        tables[i, :need] = free[at:at + need]
        at += need
        if partials:
            tables[i, 2:need:3] = n
    named = np.zeros(n, bool)
    named[tables[tables < n]] = True
    scales = {}
    if pool == "int8":
        sc = np.abs(kf).max(-1) / 127.0                  # [2, N, bs, H_kv]
        kq = np.round(kf / sc[..., None]).astype(np.int8)
        if poison:
            sc[:, ~named] = np.nan
        pools = [jnp.asarray(x) for x in kq]
        scales = dict(k_scale=jnp.asarray(sc[0]), v_scale=jnp.asarray(sc[1]))
    else:
        if poison:
            kf[:, ~named] = np.nan
        pools = [jnp.asarray(x, jnp.bfloat16) for x in kf]
    args = (q, *pools, jnp.asarray(tables), np.asarray(offs, np.int32),
            np.asarray(cls, np.int32))
    return args, scales, np.asarray(cls) > 0


def _close_on_live(got, want, cls, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    for i, n in enumerate(cls):
        assert not n or np.abs(got[i, :n] - want[i, :n]).max() < tol, i
        assert np.all(got[i, n:] == 0), f"row {i}: padding not zero"


@pytest.mark.parametrize(
    "case", GRID, ids=["-".join(map(str, g)) for g in GRID])
def test_chunk_kernel_grid(case, monkeypatch):
    """The kernel against the gather on live positions; zeros on dead rows
    and past ``chunk_lens``; under ``partials`` the raw triple."""
    heads, c, rows, offsets, window, pool, partials = case
    h_kv = HEADS[heads][1]
    # four blocks a compute block: the loop, both slots, a ragged last one
    monkeypatch.setattr(pa, "_DECODE_BUFFER_BYTES", 4 * _BS * h_kv * 16
                        * (1 if pool == "int8" else 2))
    rng = np.random.default_rng(GRID.index(case))
    args, scales, live = _grid_case(rng, heads, c, rows, offsets, pool,
                                    partials)
    cls = args[5]
    out = pa.paged_chunk_attention_pallas(
        *args, window=window, partials=partials, interpret=True, **scales)
    # the gather on the live rows alone: a dead row costs it a full table
    q, kp, vp, tables, offs, _ = args
    ref = pa.paged_chunk_attention_xla(
        q[live], kp, vp, tables[live], offs[live], cls[live], window=window,
        partials=partials, **scales)
    outs, refs = (out, ref) if partials else ((out,), (ref,))
    full = [np.zeros(x.shape, np.float32) for x in outs]
    for f, r in zip(full, refs):
        f[live] = np.asarray(r, np.float32)
    if not partials:
        _close_on_live(out, full[0], cls, 3e-2)
        return
    acc, m, l = out
    scale = max(1.0, float(np.abs(full[2]).max()))
    _close_on_live(acc, full[0], cls, 3e-2 * scale)
    _close_on_live(l, full[2], cls, 1e-2 * scale)
    # m where a query has an owned key in sight; nothing elsewhere
    seen = (full[2] > 0) & (np.arange(c)[None, :, None] < cls[:, None, None])
    assert np.abs(np.where(seen, np.asarray(m) - full[1], 0)).max() < 1e-2
    assert np.all(np.asarray(m)[~seen] <= -1e29)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_chunk_kernel_reads_no_dead_kv(pool, monkeypatch):
    """Every pool block that no live table entry names holds NaN (an int8
    pool: NaN scales): a kernel that walked past a row's frontier, read a
    dead row, or followed the sentinel would return it."""
    monkeypatch.setattr(pa, "_DECODE_BUFFER_BYTES", 4 * _BS * 8 * 16 * 2)
    outs = []
    for poison in (False, True):
        args, scales, _ = _grid_case(np.random.default_rng(11), "gqa32_8",
                                     44, "dead_between", "mid_block", pool,
                                     False, poison=poison)
        outs.append(np.asarray(pa.paged_chunk_attention_pallas(
            *args, interpret=True, **scales), np.float32))
    clean, poisoned = outs
    assert np.isfinite(poisoned).all()
    assert np.array_equal(clean, poisoned)
    assert np.abs(clean[0]).max() > 0 and np.all(clean[1] == 0)


# ----------------------------------------------- dispatch + fallback

def test_dispatch_interpret_mode(monkeypatch):
    """The rule's two sides on one slab Mosaic can copy (head_dim 128):
    off the TPU the gather; where ``mosaic_kernels_apply`` says so the
    kernel (``interpret=None`` resolves to interpreted on the CPU)."""
    rng = np.random.default_rng(5)
    q, kp, vp, tables, offs, cls = _ragged_case(
        rng, 2, 4, 4, 2, 128, 8, 4, 16, offs=[0, 9], cls=[4, 3])
    ref = pa.paged_chunk_attention_xla(q, kp, vp, tables, offs, cls)
    pa._trace_events.clear()
    out = pa.paged_chunk_attention(q, kp, vp, tables, offs, cls)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert pa._trace_events == ["chunk:xla"]
    monkeypatch.setattr(pa, "mosaic_kernels_apply", lambda: True)
    pa._trace_events.clear()
    out = pa.paged_chunk_attention(q, kp, vp, tables, offs, cls)
    _assert_live_parity(out, ref, cls)
    assert pa._trace_events == ["chunk:pallas"]


@pytest.mark.parametrize("kernel", ["decode", "chunk"])
def test_pallas_failure_raises_no_downgrade(monkeypatch, kernel):
    """On TPU a Pallas trace failure is the caller's error: nothing is
    cached, nothing downgrades to the XLA gather path, every call raises."""
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("mosaic says no")

    rng = np.random.default_rng(6)
    if kernel == "chunk":
        monkeypatch.setattr(pa, "paged_chunk_attention_pallas", boom)
        # head_dim 128: a slab the kernels can copy (decode_slab_is_tiled)
        q, kp, vp, tables, offs, cls = _ragged_case(
            rng, 2, 4, 4, 2, 128, 8, 4, 16, offs=[0, 9], cls=[4, 3])
        call = lambda: pa.paged_chunk_attention(q, kp, vp, tables, offs,
                                                cls)
    else:
        monkeypatch.setattr(pa, "paged_decode_attention_pallas", boom)
        q = jnp.asarray(rng.normal(size=(2, 4, 128)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(16, 8, 2, 128)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(16, 8, 2, 128)), jnp.float32)
        tables = jnp.asarray([[0, 1, 16, 16], [2, 3, 16, 16]], jnp.int32)
        lens = jnp.asarray([10, 13], jnp.int32)
        call = lambda: pa.paged_decode_attention(q, kp, vp, tables, lens)

    pa._trace_events.clear()
    for _ in range(2):
        with pytest.raises(RuntimeError, match="mosaic says no"):
            call()
    assert len(calls) == 2, "a failed kernel must not be remembered"
    assert "chunk:xla" not in pa._trace_events
    assert not hasattr(pa, "_pallas_disabled")


# ------------------------------------------------ engine-level helpers

def _eng_kw(**kw):
    base = dict(num_slots=4, block_size=8, max_prompt_len=8,
                max_seq_len=64)
    base.update(kw)
    return base


def _run(eng, prompts, max_new=8, **kw):
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new, **kw))
    return {r: list(map(int, t)) for r, t in eng.run().items()}


def _prompts(n, rs, lo=12, hi=24):
    # longer than max_prompt_len=8: every prompt takes the chunk program
    return [rs.randint(0, 64, (int(l),))
            for l in rs.randint(lo, hi, size=n)]


@pytest.fixture(scope="module")
def model():
    # head_dim 128 and 4 K/V heads: slabs Mosaic can copy from a float32
    # and from an int8 pool, so the dispatchers' rule on shapes
    # (decode_slab_is_tiled) takes the kernels where they apply
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=1024,
                           num_attention_heads=8, num_key_value_heads=4,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture
def kernels_on(monkeypatch):
    """Switch the engine's programs to the side of the dispatchers' rule
    a TPU takes: both paged kernels, interpreted off the TPU."""
    def on():
        monkeypatch.setattr(pa, "mosaic_kernels_apply", lambda: True)
        clear_jit_caches()
        pa._trace_events.clear()
    return on


# --------------------------------------------- engine greedy identity

@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_identity_chunked_prefill(model, kernels_on, kv_dtype):
    rs = np.random.RandomState(8)
    prompts = _prompts(5, rs)
    kw = _eng_kw(kv_dtype=kv_dtype)
    pa._trace_events.clear()
    base = _run(LLMEngine(model, **kw), prompts)
    assert "chunk:xla" in pa._trace_events          # CPU: the gather
    kernels_on()
    assert _run(LLMEngine(model, **kw), prompts) == base
    assert {"chunk:pallas", "decode:pallas"} <= set(pa._trace_events)
    assert "chunk:xla" not in pa._trace_events


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_identity_spec_decode(model, kernels_on, kv_dtype):
    """Spec verify rides the same chunk program — identity must hold
    with a draft in the loop (draft == target: the all-accept extreme)."""
    rs = np.random.RandomState(9)
    prompts = _prompts(4, rs)
    kw = _eng_kw(draft_model=model, kv_dtype=kv_dtype)
    base = _run(LLMEngine(model, **kw), prompts)
    kernels_on()
    assert _run(LLMEngine(model, **kw), prompts) == base
    assert "chunk:pallas" in pa._trace_events


def test_engine_identity_preempt_replay_interpret(model, kernels_on):
    """Interpreted kernel under preemption chaos: replay re-prefills
    through the chunk program and must still match the baseline."""
    rs = np.random.RandomState(10)
    prompts = _prompts(4, rs, lo=10, hi=18)
    kw = _eng_kw(num_blocks=24, preemption=True)
    base = _run(LLMEngine(model, **kw), prompts)
    kernels_on()
    FAULTS.install("serving.preempt", every=3, times=4,
                   action=lambda ctx: ctx["engine"]._preempt())
    try:
        out = _run(LLMEngine(model, **kw), prompts)
    finally:
        FAULTS.clear()
    assert out == base
    assert "chunk:pallas" in pa._trace_events
