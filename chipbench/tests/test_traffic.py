import json
from pathlib import Path

import numpy as np

from chipbench.traffic import sessions

MIXES = [p for p in sorted((Path(__file__).parents[1] / "traffic").glob("*.json"))
         if json.loads(p.read_text())["generator"] == "sessions"]


def _mix(path):
    return json.loads(path.read_text())["params"]


def test_pure_function_of_seed_and_parameters():
    p = _mix(MIXES[0])
    a, b = sessions.requests(7, p, 32000), sessions.requests(7, p, 32000)
    c = sessions.requests(2 ** 31 + 9, p, 32000)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["due"] == y["due"]
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


def test_every_seed_replays_the_same_trace_with_other_tokens():
    for path in MIXES:
        p = _mix(path)
        a, b = (sessions.requests(s, p, 32000) for s in (1, 2))
        shape = lambda run: [(len(r["prompt"]), r["max_new_tokens"],
                              r["shared"], r["due"]) for r in run]
        assert shape(a) == shape(b)
        assert sorted(shape(a)) != shape(a)


def test_length_quantiles_match_the_file():
    for path in MIXES:
        p = _mix(path)
        run = sessions.requests(3, p, 32000)
        drawn = np.array([len(r["prompt"]) for r in run if r["shared"] < 0])
        held = p.get("in_flight", 0)          # those are cut short
        out = np.array([r["max_new_tokens"] for r in run[held:]])
        assert abs(np.median(out) / p["output_len"]["median"] - 1) < 0.05
        assert abs(np.median(drawn) / p["prompt_len"]["median"] - 1) < 0.15
        assert out.min() >= p["output_len"]["min"]
        assert out.max() <= p["output_len"]["max"]
        assert max(len(r["prompt"]) for r in run) <= p["prompt_len"]["max"]
        share = np.mean([r["shared"] >= 0 for r in run])
        assert abs(share - p["shared"]["share"]) < 0.01
        # each stratified block does nearly the same work
        block = p["block"]
        work = [sum(r["max_new_tokens"] for r in run[i:i + block])
                for i in range(block * -(-held // block), len(run), block)]
        assert np.std(work) / np.mean(work) < 0.1


def test_requests_in_flight_are_at_staggered_points_of_their_answers():
    p = dict(_mix(MIXES[0]), in_flight=16)
    whole = sessions.requests(3, dict(p, in_flight=0), 32000)
    run = sessions.requests(3, p, 32000)
    left = sorted(r["max_new_tokens"] / w["max_new_tokens"]
                  for r, w in zip(run[:16], whole[:16]))
    assert np.allclose(left, (np.arange(16) + 0.5) / 16, atol=0.1)
    assert all(r["max_new_tokens"] >= 1 for r in run[:16])
    assert all(r["max_new_tokens"] == w["max_new_tokens"]
               and np.array_equal(r["prompt"], w["prompt"])
               for r, w in zip(run[16:], whole[16:]))
    assert all(np.array_equal(r["prompt"], w["prompt"])
               for r, w in zip(run[:16], whole[:16]))


def test_open_loop_rate_and_backlog():
    p = dict(_mix(MIXES[0]), rate=2.0)
    run = sessions.requests(5, p, 32000)
    due = np.array([r["due"] for r in run])
    assert (np.diff(due) >= 0).all() and due[0] == 0.0
    assert abs(len(run) / due[-1] / 2.0 - 1) < 0.05
    gaps = np.diff(due)
    assert 0.8 < np.std(gaps) / np.mean(gaps) < 1.2      # exponential: CV 1
    p["rate"] = "backlog"
    assert all(r["due"] == 0.0 for r in sessions.requests(5, p, 32000))


def test_token_batches_are_a_pure_function_of_seed_and_step():
    from chipbench.traffic import token_batches
    p = {"batch": 4, "seq_len": 32}
    ids, labels = token_batches.batch(2 ** 31 + 3, 5, p, 1000)
    again, _ = token_batches.batch(2 ** 31 + 3, 5, p, 1000)
    other, _ = token_batches.batch(2 ** 31 + 3, 6, p, 1000)
    assert np.array_equal(ids, again) and not np.array_equal(ids, other)
    assert len({tuple(r) for r in ids}) == 4               # rows all differ
    assert np.array_equal(labels[:, :-1], ids[:, 1:]) and (labels[:, -1] == -100).all()
