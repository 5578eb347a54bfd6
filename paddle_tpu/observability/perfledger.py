"""Bench-history perf ledger (ISSUE 12): the tool that READS the bench
artifacts the repo has been accumulating.

Every bench round leaves a ``BENCH_rNN.json`` artifact ({"n", "cmd",
"rc", "tail", "parsed"}) and ``bench.py`` appends the result line of
each run it performs to ``BENCH_HISTORY.jsonl`` next to itself. Until
now nothing read them back — five artifact files and no trajectory.
This module parses the history into per-leg series (the headline
tokens/sec, MFU, the per-config values under ``extra.configs``, and
every ``metrics.*`` sub-object's speedup), computes the newest round's
deltas against the previous parseable round, and renders a
markdown/JSON verdict with a configurable regression threshold.

Comparability: a degraded round (an old CPU-smoke artifact) is
never compared against an on-chip round — such a pair yields
``incomparable`` verdicts and cannot fail the gate. All GATED legs are
greater-is-better (throughputs, MFU, speedups). Memory legs
(``kv_bytes_per_token`` and the per-state ``kv_peak_*`` occupancy from
the KV memory ledger, ISSUE 13) are TRACKED as trajectories but never
gated: lower bytes-per-token is better and peak occupancy is
workload-shaped, so the greater-is-better regression rule does not
apply — they get a ``tracked`` verdict instead.

Deliberately **pure stdlib, zero imports from this package**: bench.py
loads this file via ``importlib.util.spec_from_file_location`` for its
``--ledger-check`` mode, which must never import jax or the
``paddle_tpu`` root.

CLI::

    python -m paddle_tpu.observability.perfledger            # markdown
    python -m paddle_tpu.observability.perfledger --json
    python -m paddle_tpu.observability.perfledger --check    # rc 1 on
                                                             # regression
    python bench.py --ledger-check                           # same gate
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Optional

__all__ = ["DEFAULT_THRESHOLD", "HISTORY_BASENAME", "append_history",
           "flatten_legs", "load_rounds", "build_report",
           "render_markdown", "main"]

DEFAULT_THRESHOLD = 0.05          # a leg must drop >5% to count as regressed
HISTORY_BASENAME = "BENCH_HISTORY.jsonl"

# leg-name markers for memory-ledger trajectories: tracked, never gated
# (not greater-is-better, so the regression rule would misfire)
_TRACKED_MARKERS = (":kv_bytes_per_token", ":kv_peak_")

_NUM = (int, float)


def _gated(leg: str) -> bool:
    """Whether a leg participates in the regression gate."""
    return not any(m in leg for m in _TRACKED_MARKERS)


def _num(v) -> Optional[float]:
    return float(v) if isinstance(v, _NUM) and not isinstance(v, bool) \
        else None


def flatten_legs(parsed) -> dict:
    """One bench result line → flat {leg name: value}. Legs: the
    headline ``value``, ``extra.mfu`` (when measured, i.e. > 0), every
    ``extra.configs.<name>.value``, and every ``metrics.<name>``
    sub-object's first of speedup/tokens_per_sec/value."""
    legs: dict = {}
    if not isinstance(parsed, dict):
        return legs
    v = _num(parsed.get("value"))
    if v is not None:
        legs["headline"] = v
    extra = parsed.get("extra")
    if isinstance(extra, dict):
        m = _num(extra.get("mfu"))
        if m is not None and m > 0.0:
            legs["mfu"] = m
        cfgs = extra.get("configs")
        if isinstance(cfgs, dict):
            for name in sorted(cfgs):
                if isinstance(cfgs[name], dict):
                    cv = _num(cfgs[name].get("value"))
                    if cv is not None:
                        legs[f"config:{name}"] = cv
    mets = parsed.get("metrics")
    if isinstance(mets, dict):
        for name in sorted(mets):
            sub = mets[name]
            if not isinstance(sub, dict) or "error" in sub:
                continue
            for key in ("speedup", "tokens_per_sec", "value"):
                sv = _num(sub.get(key))
                if sv is not None:
                    legs[f"metrics:{name}"] = sv
                    break
            # memory-ledger trajectories (ISSUE 13): per-leg HBM bytes
            # per resident token and peak occupancy by state — tracked
            # (never gated; see _TRACKED_MARKERS)
            bt = _num(sub.get("kv_bytes_per_token"))
            if bt is not None and bt > 0.0:
                legs[f"metrics:{name}:kv_bytes_per_token"] = bt
            pk = sub.get("kv_peak_blocks")
            if isinstance(pk, dict):
                for state in sorted(pk):
                    pv = _num(pk[state])
                    if pv is not None:
                        legs[f"metrics:{name}:kv_peak_{state}"] = pv
    return legs


def _round_entry(label: str, doc: dict) -> dict:
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    ok = isinstance(parsed, dict)
    return {"label": label,
            "rc": doc.get("rc") if isinstance(doc, dict) else None,
            "parsed_ok": ok,
            "degraded": bool(parsed.get("degraded")) if ok else None,
            "legs": flatten_legs(parsed)}


def load_rounds(root: str) -> list:
    """Chronological round entries: every ``BENCH_r*.json`` under
    ``root`` (sorted by filename — the round number is zero-padded),
    then the ``BENCH_HISTORY.jsonl`` lines bench.py appended itself.
    History lines whose parsed result exactly duplicates a file round
    are dropped (the driver snapshots the same run into the next
    ``BENCH_rNN.json``)."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        label = os.path.splitext(os.path.basename(path))[0]
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            rounds.append({"label": label, "rc": None, "parsed_ok": False,
                           "degraded": None, "legs": {},
                           "error": f"{type(e).__name__}: {e}"})
            continue
        rounds.append(_round_entry(label, doc))
    seen = [r["legs"] for r in rounds if r["parsed_ok"]]
    hist = os.path.join(root, HISTORY_BASENAME)
    if os.path.exists(hist):
        try:
            with open(hist) as f:
                lines = f.read().splitlines()
        except OSError:
            lines = []
        n = 0
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            n += 1
            entry = _round_entry(f"run{n:02d}", {"rc": 0, "parsed": doc})
            if entry["parsed_ok"] and entry["legs"] in seen:
                continue
            rounds.append(entry)
    return rounds


def append_history(result: dict, root: str) -> bool:
    """Append one bench result line to the ledger (bench.py calls this
    at the end of every orchestrated run). Never raises — a read-only
    checkout must not break the bench itself."""
    try:
        with open(os.path.join(root, HISTORY_BASENAME), "a") as f:
            f.write(json.dumps(result, sort_keys=True,
                               separators=(",", ":")) + "\n")
        return True
    except OSError:
        return False


def build_report(rounds: list, threshold: float = DEFAULT_THRESHOLD) -> dict:
    """Trajectory + newest-vs-previous deltas + per-leg verdicts.
    Verdicts: ``regressed``/``ok``/``improved`` (beyond ±threshold) when
    the newest two parseable rounds are comparable (same degraded flag),
    ``incomparable`` otherwise, ``new``/``missing`` when only one side
    has the leg, ``tracked`` for memory-ledger legs (trajectory only —
    never gated). ``status`` is ``fail`` iff something regressed."""
    leg_names: list = []
    for r in rounds:
        for leg in r["legs"]:
            if leg not in leg_names:
                leg_names.append(leg)
    trajectory = {leg: [(r["label"], r["legs"].get(leg)) for r in rounds]
                  for leg in leg_names}
    parseable = [r for r in rounds if r["parsed_ok"]]
    newest = parseable[-1] if parseable else None
    prev = parseable[-2] if len(parseable) >= 2 else None
    comparable = (newest is not None and prev is not None
                  and newest["degraded"] == prev["degraded"])
    legs: dict = {}
    if newest is not None:
        union = list(newest["legs"])
        if prev is not None:
            union += [leg for leg in prev["legs"] if leg not in union]
        for leg in union:
            new = newest["legs"].get(leg)
            old = prev["legs"].get(leg) if prev is not None else None
            if new is None:
                verdict, pct = "missing", None
            elif old is None:
                verdict, pct = "new", None
            elif not comparable:
                verdict, pct = "incomparable", None
            else:
                pct = (new - old) / old if old else 0.0
                if not _gated(leg):
                    verdict = "tracked"     # memory leg: trajectory only
                else:
                    verdict = ("regressed" if pct < -threshold else
                               "improved" if pct > threshold else "ok")
            legs[leg] = {"new": new, "old": old, "delta_pct": pct,
                         "verdict": verdict}
    regressed = sorted(k for k, v in legs.items()
                       if v["verdict"] == "regressed")
    return {"rounds": [{k: r.get(k) for k in
                        ("label", "rc", "parsed_ok", "degraded")}
                       for r in rounds],
            "trajectory": trajectory,
            "newest": newest["label"] if newest else None,
            "previous": prev["label"] if prev else None,
            "comparable": comparable,
            "threshold": threshold,
            "legs": legs,
            "regressed": regressed,
            "status": "fail" if regressed else "ok"}


def _fmt(v) -> str:
    if v is None:
        return "—"
    return f"{v:g}"


def render_markdown(report: dict) -> str:
    """The human verdict: a trajectory table (legs × rounds) and the
    newest-vs-previous delta table."""
    labels = [r["label"] for r in report["rounds"]]
    flags = ["✗" if not r["parsed_ok"] else
             "degraded" if r["degraded"] else "on-chip"
             for r in report["rounds"]]
    lines = ["# bench trajectory", "",
             "| leg | " + " | ".join(labels) + " |",
             "|-----|" + "|".join("---" for _ in labels) + "|",
             "| *(round)* | " + " | ".join(flags) + " |"]
    for leg, series in report["trajectory"].items():
        lines.append("| " + leg + " | "
                     + " | ".join(_fmt(v) for _, v in series) + " |")
    lines += ["",
              f"## {report['newest'] or '—'} vs {report['previous'] or '—'}"
              f" (threshold ±{report['threshold']:.0%})", ""]
    if not report["legs"]:
        lines.append("no parseable rounds to compare.")
    else:
        if not report["comparable"]:
            lines.append("rounds are not comparable (degraded vs on-chip) "
                         "— deltas withheld.")
            lines.append("")
        lines += ["| leg | old | new | delta | verdict |",
                  "|-----|-----|-----|-------|---------|"]
        for leg, d in report["legs"].items():
            pct = ("—" if d["delta_pct"] is None
                   else f"{d['delta_pct']:+.1%}")
            lines.append(f"| {leg} | {_fmt(d['old'])} | {_fmt(d['new'])} "
                         f"| {pct} | {d['verdict']} |")
    lines += ["", f"**status: {report['status']}**"
              + (f" — regressed: {', '.join(report['regressed'])}"
                 if report["regressed"] else "")]
    return "\n".join(lines) + "\n"


def _default_root() -> str:
    """The repo root (two package levels up from this file) — where the
    driver's BENCH_r*.json artifacts live."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perfledger",
        description="parse BENCH_r*.json history into a per-leg "
                    "trajectory and a regression verdict")
    ap.add_argument("--dir", default=_default_root(),
                    help="directory holding BENCH_r*.json "
                         "(default: the repo root)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative drop that counts as a regression "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full JSON report instead of markdown")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the newest round regresses a leg "
                         "past the threshold")
    args = ap.parse_args(argv)
    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"perfledger: no BENCH_r*.json under {args.dir}")
        return 2 if args.check else 0
    report = build_report(rounds, threshold=args.threshold)
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        print(render_markdown(report), end="")
    if args.check and report["status"] == "fail":
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
