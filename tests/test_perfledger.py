"""Bench-history perf ledger (ISSUE 12): the ledger parses every
``BENCH_r0*.json`` of a tree (fixture files under ``tmp_path``, shaped
like the driver's artifacts), plus synthetic-history coverage of the regression verdicts, comparability
rules, the history append path, and the CLI exit codes.

The module under test is deliberately pure stdlib (``bench.py
--ledger-check`` loads it by file path and must never import jax); the
import here goes through the package like any other test."""
import json
import os
import pathlib

import pytest

from paddle_tpu.observability import perfledger as pl


# --------------------------------------------- a history of five rounds
@pytest.fixture
def history(tmp_path):
    """Five round artifacts shaped like the driver's: a crashed round
    with no result line, two degraded CPU smokes, two on-chip rounds."""
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_r01.json"), "w") as f:
        json.dump({"n": 1, "cmd": "python bench.py", "rc": 1,
                   "tail": "Traceback (most recent call last): ...",
                   "parsed": None}, f)
    cpu = {"flash": False, "mfu": 0.0, "device": "TFRT_CPU_0"}
    chip = {"flash": True, "mfu": 0.589, "device": "TPU v5 lite0",
            "configs": {"resnet50": {"images_per_sec": 1020.0}}}
    _write_round(root, 2, 17662.7, degraded=True, extra=cpu)
    _write_round(root, 3, 20677.3, degraded=True, extra=cpu)
    _write_round(root, 4, 25012.0, extra=chip)
    _write_round(root, 5, 25024.2, extra=chip)
    return root


def test_ledger_parses_every_bench_round_in_the_tree(history):
    """Acceptance criterion: every BENCH_r0*.json in a tree parses
    into the trajectory, a round without a result line included."""
    files = sorted(p.name for p in pathlib.Path(history).glob("BENCH_r*.json"))
    assert len(files) == 5
    rounds = pl.load_rounds(history)
    labels = [r["label"] for r in rounds]
    for f in files:
        assert os.path.splitext(f)[0] in labels
    by_label = {r["label"]: r for r in rounds}
    assert not by_label["BENCH_r01"]["parsed_ok"]
    # rounds that recorded a parseable result line must flatten to legs
    parseable = [r for r in rounds if r["parsed_ok"]]
    assert len(parseable) == 4
    for r in parseable:
        assert r["legs"], f"{r['label']} parsed but yielded no legs"
        assert all(isinstance(v, float) for v in r["legs"].values())
        assert r["degraded"] in (True, False)
    # the two newest artifacts are on-chip rounds with a headline leg
    for lbl in ("BENCH_r04", "BENCH_r05"):
        assert by_label[lbl]["parsed_ok"], f"{lbl} must parse"
        assert "headline" in by_label[lbl]["legs"]


def test_ledger_report_and_markdown_render_from_repo_history(history):
    rounds = pl.load_rounds(history)
    report = pl.build_report(rounds)
    n = len(rounds)
    assert report["trajectory"], "no legs tracked at all"
    for leg, series in report["trajectory"].items():
        assert len(series) == n, f"{leg} series misses rounds"
    assert report["newest"] is not None
    assert report["status"] in ("ok", "fail")
    md = pl.render_markdown(report)
    assert md.startswith("# bench trajectory")
    assert f"**status: {report['status']}**" in md
    for r in rounds:
        assert r["label"] in md


def test_ledger_cli_runs_on_the_repo(history, capsys):
    assert pl.main(["--dir", history]) == 0         # report always renders
    out = capsys.readouterr().out
    assert "# bench trajectory" in out
    assert pl.main(["--dir", history, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) >= {"rounds", "trajectory", "legs", "status"}


# ------------------------------------------------------- synthetic history
def _write_round(root, n, value, degraded=False, extra=None, metrics=None):
    parsed = {"value": value, "degraded": degraded}
    if extra:
        parsed["extra"] = extra
    if metrics:
        parsed["metrics"] = metrics
    doc = {"n": n, "rc": 0, "tail": "", "parsed": parsed}
    with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump(doc, f)


def test_regression_verdict_and_check_exit_code(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0,
                 extra={"mfu": 0.31, "configs": {"a": {"value": 10.0}}},
                 metrics={"spec": {"speedup": 2.0},
                          "broken": {"error": "boom"}})
    _write_round(root, 2, 80.0,
                 extra={"mfu": 0.33, "configs": {"a": {"value": 10.2}}},
                 metrics={"spec": {"speedup": 2.05}})
    report = pl.build_report(pl.load_rounds(root))
    assert report["comparable"]
    assert report["legs"]["headline"]["verdict"] == "regressed"
    assert report["legs"]["headline"]["delta_pct"] == pytest.approx(-0.2)
    assert report["legs"]["mfu"]["verdict"] == "improved"   # +6.5% > 5%
    assert report["legs"]["config:a"]["verdict"] == "ok"    # +2% within
    assert report["legs"]["metrics:spec"]["verdict"] == "ok"
    assert "metrics:broken" not in report["legs"]   # error subs skipped
    assert report["status"] == "fail"
    assert report["regressed"] == ["headline"]
    assert pl.main(["--dir", root, "--check"]) == 1
    assert pl.main(["--dir", root, "--check", "--threshold", "0.5"]) == 0
    assert pl.main(["--dir", root]) == 0            # no --check: report only


def test_degraded_round_is_never_compared_against_on_chip(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0, degraded=False)
    _write_round(root, 2, 5.0, degraded=True)       # CPU smoke: 20x slower
    report = pl.build_report(pl.load_rounds(root))
    assert not report["comparable"]
    assert report["legs"]["headline"]["verdict"] == "incomparable"
    assert report["status"] == "ok"                 # cannot fail the gate
    assert "not comparable" in pl.render_markdown(report)
    assert pl.main(["--dir", root, "--check"]) == 0


def test_new_and_missing_legs(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0, extra={"configs": {"old": {"value": 1.0}}})
    _write_round(root, 2, 101.0, extra={"configs": {"new": {"value": 2.0}}})
    legs = pl.build_report(pl.load_rounds(root))["legs"]
    assert legs["config:new"]["verdict"] == "new"
    assert legs["config:old"]["verdict"] == "missing"
    assert legs["headline"]["verdict"] == "ok"


def test_unparseable_round_is_flagged_not_fatal(tmp_path):
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_r01.json"), "w") as f:
        f.write("{not json")
    _write_round(root, 2, 50.0)
    rounds = pl.load_rounds(root)
    assert [r["parsed_ok"] for r in rounds] == [False, True]
    report = pl.build_report(rounds)
    assert report["newest"] == "BENCH_r02"
    assert report["previous"] is None
    md = pl.render_markdown(report)
    assert "✗" in md                                 # the broken round shows


def test_append_history_roundtrip_and_dedup(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0)
    assert pl.append_history({"value": 90.0, "degraded": False}, root)
    rounds = pl.load_rounds(root)
    assert [r["label"] for r in rounds] == ["BENCH_r01", "run01"]
    assert rounds[-1]["legs"]["headline"] == 90.0
    # a history line identical to a file round is the same run snapshotted
    # by the driver — it must not appear twice
    assert pl.append_history({"value": 100.0}, root)
    rounds = pl.load_rounds(root)
    assert [r["label"] for r in rounds] == ["BENCH_r01", "run01"]


def test_empty_dir_exit_codes(tmp_path, capsys):
    assert pl.main(["--dir", str(tmp_path)]) == 0
    assert pl.main(["--dir", str(tmp_path), "--check"]) == 2
    assert "no BENCH_r*.json" in capsys.readouterr().out


def test_flatten_legs_ignores_junk():
    assert pl.flatten_legs(None) == {}
    assert pl.flatten_legs({"value": "fast"}) == {}      # non-numeric
    assert pl.flatten_legs({"value": True}) == {}        # bool is not a leg
    legs = pl.flatten_legs({"value": 3, "extra": {"mfu": 0.0}})
    assert legs == {"headline": 3.0}                     # mfu 0.0 = unmeasured
