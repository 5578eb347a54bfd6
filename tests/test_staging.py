"""A forward program is handed its host arrays as one vector (ISSUE 40).

``models.paged.Staging`` is the layout, written once: the executor packs a
call's small numpy arrays into one fresh int32 vector and the program's
first lines take it apart. Held here: pack -> unpack gives back every
field, bit for bit; an engine served through the staged programs emits
the tokens the array-signature bodies give when they are handed the
arrays as before (a LLaMA-shaped, a looped and a hybrid model); and the
``exe.dispatch`` edge of the three forwards counts one upload.

All CPU, nothing timed.
"""
import inspect
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import paged
from paddle_tpu.models.paged import Staging, prefill_staging, tick_staging
from paddle_tpu.observability import TRACER
from paddle_tpu.serving import LLMEngine, ModelExecutor, Request
from paddle_tpu.serving import executor as executor_mod

CONFIGS = Path(pt.__file__).parents[1] / "chipbench" / "tests" / "cells" \
    / "configs"
FAMILIES = {"llama": ("tiny.json", "llama"), "ouro": ("tiny-ouro.json", "ouro"),
            "hybrid": ("tiny-olmo-hybrid.json", "olmo_hybrid")}
ENGINE = dict(num_slots=4, block_size=4, max_prompt_len=16, max_seq_len=128,
              num_blocks=64)


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    import importlib
    file, builder = FAMILIES[request.param]
    cfg = json.loads((CONFIGS / file).read_text())
    build = importlib.import_module("chipbench.builders." + builder).build
    return request.param, build(cfg, 2 ** 31 + 40).eval()


def _engine(family, **kw):
    name, model = family
    extra = {"num_state_snapshots": 4} if name == "hybrid" else {}
    return LLMEngine(model, **{**ENGINE, **extra, **kw})


def _host_unpack(layout: Staging, vec):
    """The inverse of ``Staging.pack`` on the host, written apart from
    ``Staging.unpack``: each field from its own slice of the vector."""
    out = []
    for _, dtype, shape, lo, hi in layout.fields:
        x = np.array(vec[lo:hi])
        x = (x.view(np.float32) if dtype == "float32"
             else x.astype(bool) if dtype == "bool" else x)
        out.append(x.reshape(shape))
    return out


# ------------------------------------------------------ pack and unpack

# float32 values whose bits a cast through any other type would lose
BITS = np.array([0x00000000, 0x80000000, 0x00000001, 0x7F800000, 0xFF800000,
                 0x7FC00001, 0xFFC12345, 0x3F800000, 0x3F7FFFFF, 0x00800000,
                 0x7F7FFFFF, 0x3DCCCCCD], np.uint32).view(np.float32)
N = len(BITS)
FIELDS = {
    "int32": np.array([0, 1, -1, 2 ** 31 - 1, -2 ** 31, N, 63, 4096, 7, 8,
                       9, 10], np.int32),           # N: a sentinel row
    "bool": np.arange(N) % 3 == 0,
    "float32": BITS,
}


@pytest.mark.parametrize("dtype", list(FIELDS))
def test_every_dtype_comes_back_bit_for_bit(dtype):
    """One field of each dtype between two others, through the jitted
    unpack: same dtype, same shape, same bits."""
    layout = Staging(before=("int32", (3,)), x=(dtype, (N,)),
                     after=("float32", (2, 2)))
    x = FIELDS[dtype]
    vec = layout.pack([1, 2, 3], x, np.full((2, 2), 0.5, np.float32))
    assert vec.dtype == np.int32 and vec.shape == (layout.size,) == (N + 7,)
    before, got, after = jax.jit(layout.unpack)(vec)
    assert got.dtype == x.dtype and got.shape == x.shape
    same = np.int32 if dtype == "float32" else x.dtype
    np.testing.assert_array_equal(np.asarray(got).view(same), x.view(same))
    np.testing.assert_array_equal(before, [1, 2, 3])
    np.testing.assert_array_equal(after, np.full((2, 2), 0.5, np.float32))
    host = _host_unpack(layout, vec)[1]
    np.testing.assert_array_equal(host.view(same), x.view(same))


@pytest.mark.parametrize("layout,shapes", [
    (tick_staging(5), [(5,)] * 7),
    (prefill_staging(2, 8, 6, False), [(2, 8), (2,), (2,), (2, 6)]),
    (prefill_staging(2, 8, 6, True), [(2, 8), (2,), (2,), (2,), (2, 6)]),
], ids=["tick", "prefill", "chunk"])
def test_the_three_layouts_round_trip_their_fields(layout, shapes):
    """Random values in every field of the programs' own layouts."""
    rs = np.random.RandomState(5)
    arrays = []
    for (name, dtype, shape, _, _), want in zip(layout.fields, shapes):
        assert shape == want
        a = (rs.rand(*shape) > 0.5 if dtype == "bool"
             else rs.rand(*shape).astype(np.float32) if dtype == "float32"
             else rs.randint(0, 2 ** 20, shape).astype(np.int32))
        arrays.append(a)
    got = jax.jit(layout.unpack)(layout.pack(*arrays))
    assert len(got) == len(arrays) == len(shapes)
    for a, g in zip(arrays, got):
        assert g.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(g), a)


def test_pack_gives_a_fresh_vector_and_casts_to_the_layout():
    """Never a view of what it was handed (the engine changes its mirrors
    while a transfer may be pending); wider host dtypes are cast as
    ``jnp.asarray`` cast them."""
    layout = Staging(a=("int32", (4,)), t=("float32", (4,)))
    a, t = np.arange(4, dtype=np.int32), np.ones(4, np.float32)
    v1, v2 = layout.pack(a, t), layout.pack(a, t)
    assert not np.shares_memory(v1, a) and not np.shares_memory(v1, t)
    assert not np.shares_memory(v1, v2)
    a[0] = 99
    assert v1[0] == 0
    wide = layout.pack(np.arange(4, dtype=np.int64), [0.1, 0.2, 0.3, 0.4])
    got = jax.jit(layout.unpack)(wide)
    np.testing.assert_array_equal(got[0], np.arange(4, dtype=np.int32))
    np.testing.assert_array_equal(
        got[1], np.asarray([0.1, 0.2, 0.3, 0.4], np.float32))
    # a strided view is packed by value
    np.testing.assert_array_equal(
        _host_unpack(layout, layout.pack(np.arange(8, dtype=np.int32)[::2],
                                         t))[0], [0, 2, 4, 6])


def test_a_layout_refuses_what_it_was_not_laid_out_for():
    layout = tick_staging(3)
    z = np.zeros(3, np.int32)
    with pytest.raises(ValueError, match="upd_cols.*shape"):
        layout.pack(z, z, z, np.zeros(4, np.int32), z, z, z)
    with pytest.raises(TypeError, match="7 arrays"):
        layout.pack(z, z, z)
    with pytest.raises(TypeError, match="int64"):
        Staging(x=("int64", (3,)))
    # compared and hashed by its fields: engines of one shape share a trace
    assert Staging(a=("int32", (2,))) == Staging(a=("int32", (2,)))
    assert hash(Staging(a=("int32", (2,)))) == hash(Staging(a=("int32", (2,))))
    assert Staging(a=("int32", (2,))) != Staging(a=("bool", (2,)))
    assert Staging(a=("int32", (2,))) != Staging(b=("int32", (2,)))


# ----------------------------------- the same tokens as the bodies alone

# the three bodies under the jits the executor held before they were
# staged: what is compared with, never what serves
_BODY_TICK = jax.jit(paged.llama_decode_tick, static_argnums=(10, 11),
                     donate_argnums=(2,))
_BODY_PREFILL = jax.jit(paged.llama_prefill_paged, donate_argnums=(3,))
_BODY_CHUNK = jax.jit(paged.llama_prefill_chunk_paged, donate_argnums=(4,))


def _hand_over_arrays(monkeypatch):
    """The executor's three programs replaced by the bodies, each handed
    the arrays the host unpacks from the vector. -> calls by program."""
    calls = {"tick": 0, "prefill": 0, "chunk": 0}

    def tick(model, staged, cache, rng, layout, top_k, want_logp, **kw):
        calls["tick"] += 1
        tok, act, rows, cols, vals, temps, top_ps = _host_unpack(layout,
                                                                 staged)
        return _BODY_TICK(model, tok, cache, act, rows, cols, vals, rng,
                          temps, top_ps, top_k, want_logp, **kw)

    def prefill(model, staged, cache, layout, lora=None):
        calls["prefill"] += 1
        ids, lens, slots, rows = _host_unpack(layout, staged)
        return (*_BODY_PREFILL(model, ids, lens, cache, slots, rows,
                               lora=lora), None)      # no counts of routing

    def chunk(model, staged, cache, layout, lora=None):
        calls["chunk"] += 1
        ids, lens, offs, slots, rows = _host_unpack(layout, staged)
        return (*_BODY_CHUNK(model, ids, lens, offs, cache, slots, rows,
                             lora=lora), None)

    monkeypatch.setattr(executor_mod, "_TICK_JIT", tick)
    monkeypatch.setattr(executor_mod, "_PREFILL_JIT", prefill)
    monkeypatch.setattr(executor_mod, "_PREFILL_CHUNK_JIT", chunk)
    return calls


def _serve(eng, rounds=2):
    """Short prompts (one padded admission forward), one over
    ``max_prompt_len`` (chunks), a repeated prefix (a hit the second
    round; the hybrid's snapshot), greedy and sampled rows side by side.
    -> every request's tokens, in order."""
    rs = np.random.RandomState(3)
    doc = rs.randint(1, 200, (24,))
    out = []
    for r in range(rounds):
        rids = []
        for i, n in enumerate((5, 37, 3)):
            rids.append(eng.add_request(Request(
                rs.randint(1, 200, (n,)), max_new_tokens=7,
                temperature=0.0 if i % 2 else 0.8, top_p=0.9)))
        rids.append(eng.add_request(Request(
            np.concatenate([doc, [9 + r, 8, 7]]), max_new_tokens=7)))
        eng.run()
        out += [list(eng.requests[rid].tokens) for rid in rids]
    return out


def test_an_engine_emits_the_tokens_of_the_bodies_handed_arrays(
        family, monkeypatch):
    """The staged programs and the array-signature bodies, under one
    engine each with one seed: the same values reach the same body in the
    same dtypes, so the tokens are the same, sampled ones too."""
    staged = _serve(_engine(family, seed=11))
    calls = _hand_over_arrays(monkeypatch)
    arrays = _serve(_engine(family, seed=11))
    assert calls["tick"] > 10 and calls["chunk"] >= 3
    assert calls["prefill"] >= (0 if family[0] == "hybrid" else 1)
    assert staged == arrays
    assert all(len(t) == 7 for t in staged)


# ------------------------------------------------- one upload at the edge

def test_each_forward_program_is_sent_one_host_array(family):
    """``uploads`` on ``exe.dispatch``: the host (numpy) arrays among the
    call's arguments. One for ``tick``, ``chunk`` and ``prefill``: the
    vector. None for the key's split, whose key is on the device."""
    eng = _engine(family)
    TRACER.clear()
    TRACER.enable()
    try:
        _serve(eng, rounds=1)
    finally:
        TRACER.disable()
    sent = [e["args"] for e in TRACER.export()["traceEvents"]
            if e["ph"] == "X" and e["name"] == "exe.dispatch"]
    TRACER.clear()
    by_program = {}
    for a in sent:
        by_program.setdefault(a["program"], set()).add(a["uploads"])
    forwards = {"tick", "chunk"} | (set() if family[0] == "hybrid"
                                    else {"prefill"})
    assert forwards <= set(by_program)
    for program in forwards:
        assert by_program[program] == {1}, program
    assert by_program["split"] == {0}


def test_uploads_is_counted_only_while_spans_record(family, monkeypatch):
    """Off, the edge counts nothing: no span of the executor is handed an
    argument after it was opened."""
    class Off:
        recording = False

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set(self, **args):
            pytest.fail(f"a span that does not record was handed {args}")

    monkeypatch.setattr(executor_mod, "_span", lambda *a, **kw: Off())
    eng = _engine(family)
    rid = eng.add_request(Request(np.arange(1, 30), max_new_tokens=3))
    eng.run()
    assert len(eng.requests[rid].tokens) == 3


# ------------------------------------------------------- the entries stay

def test_the_executors_entries_keep_their_signatures(family):
    """What the benchmark's drivers and the engine call: names and order."""
    def params(fn):
        return list(inspect.signature(fn).parameters)[1:]
    # (PR 44 appended one keyword to each, the window space's tables of a
    # model with two block spaces: () for every other)
    assert params(ModelExecutor.prefill) == ["ids", "lens", "slots", "rows",
                                             "lora", "wrows"]
    assert params(ModelExecutor.prefill_chunk) == [
        "ids", "lens", "offs", "slots", "rows", "lora", "wrows"]
    assert params(ModelExecutor.decode_tick) == [
        "last_tok", "run_mask", "rows", "cols", "vals", "temps", "top_ps",
        "need_logp", "lora", "bias", "wvals"]
    # positionally, as ``chipbench/drivers/serve.py``'s counter passes them
    eng = _engine(family)
    seen = []
    for name in ("prefill", "prefill_chunk"):
        def counted(ids, lens, *a, _fn=getattr(eng.exe, name), **kw):
            seen.append((np.shape(ids), int(np.sum(lens))))
            return _fn(ids, lens, *a, **kw)
        setattr(eng.exe, name, counted)
    rid = eng.add_request(Request(np.arange(1, 30), max_new_tokens=3))
    eng.run()
    assert len(eng.requests[rid].tokens) == 3
    assert sum(useful for _, useful in seen) == 29
    assert {shape for shape, _ in seen} == {(eng.prefill_rows, 16)}
