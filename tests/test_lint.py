"""Collective-deadlock lint: catches divergent-cond collectives and
collective while-predicates; passes clean SPMD code. Plus a source-level
clock lint: durations must never come from the wall clock."""
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from paddle_tpu.utils.lint import (
    assert_no_collective_deadlock,
    lint_collectives,
)

AX = [("x", 4)]


def test_clean_collective_sequence():
    def f(v):
        s = lax.psum(v, "x")
        g = lax.all_gather(v, "x")
        return s + g.sum()

    rep = lint_collectives(f, jnp.ones(2), axis_env=AX)
    assert rep.ok
    assert [n for n, _ in rep.sequence] == ["psum", "all_gather"]


def test_cond_divergence_flagged():
    def f(v):
        return lax.cond(v.sum() > 0,
                        lambda u: lax.psum(u, "x"),
                        lambda u: u * 2,
                        v)

    rep = lint_collectives(f, jnp.ones(2), axis_env=AX)
    assert not rep.ok
    assert rep.issues[0].kind == "cond-divergence"
    with pytest.raises(RuntimeError):
        assert_no_collective_deadlock(f, jnp.ones(2), axis_env=AX)


def test_cond_symmetric_ok():
    def f(v):
        return lax.cond(v.sum() > 0,
                        lambda u: lax.psum(u * 2, "x"),
                        lambda u: lax.psum(u + 1, "x"),
                        v)

    rep = lint_collectives(f, jnp.ones(2), axis_env=AX)
    assert rep.ok
    assert [n for n, _ in rep.sequence] == ["psum"]


def test_while_cond_collective_flagged():
    def f(v):
        def cond(c):
            return lax.psum(c.sum(), "x") < 10

        def body(c):
            return c + 1

        return lax.while_loop(cond, body, v)

    rep = lint_collectives(f, jnp.ones(2), axis_env=AX)
    assert not rep.ok
    assert any(i.kind == "while-cond-collective" for i in rep.issues)


def test_nested_scan_collectives_found():
    def f(v):
        def body(c, _):
            return lax.ppermute(c, "x", [(i, (i + 1) % 4) for i in range(4)]), None

        out, _ = lax.scan(body, v, None, length=3)
        return lax.psum(out, "x")

    rep = lint_collectives(f, jnp.ones(2), axis_env=AX)
    assert rep.ok
    names = [n for n, _ in rep.sequence]
    assert names == ["ppermute", "psum"]


def test_pipeline_shard_map_body_lints_clean():
    """The PRODUCTION pipeline schedule (PipelineLayer's shard_map body)
    passes the deadlock lint — this closes the shard_map-pipeline lint
    item from SURVEY §5 against the real code, not a toy."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.distributed.pipeline import PipelineLayer
    from paddle_tpu.utils.lint import lint_collectives

    pt.seed(0)
    blocks = [nn.Sequential(nn.Linear(8, 8), nn.GELU()) for _ in range(4)]
    pipe = PipelineLayer(blocks, num_stages=4, num_microbatches=2)
    mesh = HybridMesh(pp=4, devices=__import__("jax").devices()[:4])

    # lint the whole pipelined forward: the shard_map body's collectives
    # (ppermute handoffs inside the tick scan) appear in the sequence
    rep = lint_collectives(lambda x: pipe(x, mesh=mesh),
                           jnp.ones((4, 8)))
    assert rep.ok, rep.issues
    names = [n for n, _ in rep.sequence]
    assert "ppermute" in names


# --------------------------------------------------------------- clock lint
# Durations measured with time.time() jump when NTP steps the wall clock —
# every duration in paddle_tpu must ride time.monotonic()/perf_counter or
# the observability span API. Files with a LEGITIMATE wall-clock need
# (timestamps for humans, not durations) go on the allowlist with a reason.
_WALLCLOCK_ALLOWLIST = {
    # e.g. "paddle_tpu/some/module.py": "emits human-readable timestamps",
    "paddle_tpu/observability/flight.py":
        "t_wall in dump artifacts — humans correlate crash dumps by wall "
        "clock; every duration in the module rides time.monotonic()",
    "paddle_tpu/observability/shipper.py":
        "t_wall in shipped JSONL records — cross-process correlation "
        "timestamp; intervals/deltas ride time.monotonic()",
}


def test_no_wall_clock_durations_in_paddle_tpu():
    root = pathlib.Path(__file__).resolve().parent.parent
    pkg = root / "paddle_tpu"
    pat = re.compile(r"\btime\.time\s*\(")
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        rel = str(path.relative_to(root))
        if rel in _WALLCLOCK_ALLOWLIST:
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line.split("#", 1)[0]):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "wall-clock time.time() used for timing (use time.monotonic() or "
        "the observability span API, or allowlist with a reason):\n"
        + "\n".join(offenders))


# ---------------------------------------------------------- thread-name lint
# Every background thread paddle_tpu spawns must carry a "pt-" name so the
# conftest leak fixture (and an operator's py-spy dump) can attribute any
# survivor to its subsystem. Same allowlist mechanism as the clock lint.
_THREAD_NAME_ALLOWLIST = {
    # e.g. "paddle_tpu/some/module.py": "thread name set post-construction",
}


def test_threads_carry_pt_name_prefix():
    root = pathlib.Path(__file__).resolve().parent.parent
    pkg = root / "paddle_tpu"
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        rel = str(path.relative_to(root))
        if rel in _THREAD_NAME_ALLOWLIST:
            continue
        text = path.read_text()
        for m in re.finditer(r"\bthreading\.Thread\s*\(", text):
            # the constructor call may span lines — scan a window past
            # the open paren for the name= kwarg
            window = text[m.start():m.start() + 500]
            if not re.search(r"""name\s*=\s*f?["']pt-""", window):
                lineno = text.count("\n", 0, m.start()) + 1
                offenders.append(f"{rel}:{lineno}")
    assert not offenders, (
        'threading.Thread without a name="pt-..." (the leak fixture cannot '
        "attribute unnamed survivors; allowlist with a reason if the name "
        "is set elsewhere):\n" + "\n".join(offenders))


# ------------------------------------------------------ instrument hygiene
# Every metric instrument registered under paddle_tpu/ must carry a
# non-empty help string (the generated metrics reference renders it) and
# a name under one of the approved subsystem prefixes, so the exported
# namespace stays groupable in a Prometheus/Grafana deployment.
_INSTRUMENT_PREFIXES = (
    "serving_", "router_", "train_", "io_", "ckpt_", "moe_", "compile_",
    "collective_", "elastic_", "faults_", "steptimer_", "device_",
    "python_",      # the interpreter itself: the collector's pauses
)
_INSTRUMENT_ALLOWLIST = {
    # e.g. "paddle_tpu/some/module.py": "registers dynamic names",
}


def test_metric_instruments_have_help_and_approved_prefix():
    root = pathlib.Path(__file__).resolve().parent.parent
    pkg = root / "paddle_tpu"
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        rel = str(path.relative_to(root))
        if rel in _INSTRUMENT_ALLOWLIST:
            continue
        text = path.read_text()
        for m in re.finditer(r"\bMETRICS\.(counter|gauge|histogram)\s*\(",
                             text):
            # registrations span lines — scan a window past the open
            # paren for the first two string literals (name, help)
            window = text[m.end():m.end() + 500]
            lits = re.findall(r'"((?:[^"\\]|\\.)*)"', window)
            lineno = text.count("\n", 0, m.start()) + 1
            if not lits:
                offenders.append(f"{rel}:{lineno}: no literal name")
                continue
            name = lits[0]
            if not name.startswith(_INSTRUMENT_PREFIXES):
                offenders.append(
                    f"{rel}:{lineno}: {name!r} lacks an approved prefix "
                    f"{_INSTRUMENT_PREFIXES}")
            if len(lits) < 2 or not lits[1].strip():
                offenders.append(f"{rel}:{lineno}: {name!r} has no help "
                                 "string")
    assert not offenders, (
        "metric instruments without help text or an approved name prefix "
        "(fix the registration or allowlist the file with a reason):\n"
        + "\n".join(offenders))


# ------------------------------------------------- memledger choke points
# Every block-mutating method on the KV/block-manager stack must notify
# the per-pool memory ledger (ISSUE 13) — a mutation path that skips it
# silently breaks the sum(states) == num_blocks reconciliation the chaos
# suites assert per tick. Methods that mutate only by delegating to a
# notifying method go on the allowlist with a reason.
_MEMLEDGER_FILES = ("paddle_tpu/serving/kv.py", "paddle_tpu/models/paged.py")
_MEMLEDGER_METHODS = {"allocate", "free", "free_prefix", "adopt_prefix",
                      "_evict_one", "take_copy_plan"}
_MEMLEDGER_ALLOWLIST = {
    "paddle_tpu/serving/kv.py::KVManager.allocate":
        "delegates to the block manager, whose allocate notifies",
    "paddle_tpu/serving/kv.py::KVManager.free":
        "delegates to the block manager, whose free notifies",
    "paddle_tpu/models/paged.py::RefBlockManager.allocate":
        "delegates to BlockManager.allocate, which notifies",
    "paddle_tpu/models/paged.py::TwoSpaceBlockManager.allocate":
        "delegates to each space's own allocate, which notifies its ledger",
    "paddle_tpu/models/paged.py::TwoSpaceBlockManager.free":
        "delegates to each space's own free, which notifies its ledger",
}


def test_block_mutators_notify_the_memledger():
    import ast
    root = pathlib.Path(__file__).resolve().parent.parent
    offenders = []
    for rel in _MEMLEDGER_FILES:
        text = (root / rel).read_text()
        tree = ast.parse(text)
        for cls in [n for n in tree.body if isinstance(n, ast.ClassDef)]:
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                if fn.name not in _MEMLEDGER_METHODS:
                    continue
                key = f"{rel}::{cls.name}.{fn.name}"
                if key in _MEMLEDGER_ALLOWLIST:
                    continue
                body = ast.get_source_segment(text, fn) or ""
                if "ledger." not in body:
                    offenders.append(f"{rel}:{fn.lineno}: "
                                     f"{cls.name}.{fn.name}")
    assert not offenders, (
        "block-mutating methods that never notify the memory ledger "
        "(record the transition with self.ledger.<hook>, or allowlist "
        "with a reason if a delegate notifies):\n" + "\n".join(offenders))


# -------------------------------------------- no switch from the environment
# A served program's path is chosen by the engine's constructor and by what
# a dispatcher can observe (the backend, the shapes), never by a PT_*
# variable: each such switch doubled the configurations somebody had to keep
# alive (ISSUE 30). The names below are the debts ROADMAP.md's Queue 3 lists
# for these paths, each with its reason there; the list only shrinks.
_ENV_SWITCH_PATHS = ("paddle_tpu/serving", "paddle_tpu/models/paged.py",
                     "paddle_tpu/ops/pallas")
_ENV_SWITCH_ALLOWLIST = {
    "PT_GROUPED_GEMM", "PT_CP_IMPL", "PT_ROUTER_DISAGG", "PT_DEGRADE",
    "PT_GAUGE_EVERY_S", "PT_TENANT_LABEL_CAP",
}


def _pt_env_names(text):
    """Every ``PT_*`` name a module hands to the environment: a string
    constant that is such a name and nothing else (``os.environ.get``,
    ``os.getenv``, a subscript, ``in os.environ``, or a name kept in a
    variable on its way there; prose in a docstring is longer)."""
    import ast
    return {(n.value, n.lineno) for n in ast.walk(ast.parse(text))
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and re.fullmatch(r"PT_[A-Z0-9_]+", n.value)}


def test_no_pt_env_switch_in_the_serving_core():
    root = pathlib.Path(__file__).resolve().parent.parent
    files = []
    for rel in _ENV_SWITCH_PATHS:
        path = root / rel
        files += sorted(path.rglob("*.py")) if path.is_dir() else [path]
    seen, offenders = set(), []
    for path in files:
        for name, lineno in sorted(_pt_env_names(path.read_text())):
            seen.add(name)
            if name not in _ENV_SWITCH_ALLOWLIST:
                offenders.append(f"{path.relative_to(root)}:{lineno}: {name}")
    assert not offenders, (
        "a PT_* environment variable read in the serving core (take the "
        "decision in LLMEngine's constructor or from what the dispatcher "
        "can observe):\n" + "\n".join(offenders))
    assert seen == _ENV_SWITCH_ALLOWLIST, (
        "allowlisted names no module reads any more (drop them): "
        f"{sorted(_ENV_SWITCH_ALLOWLIST - seen)}")
    # the rule sees each way of reading one
    assert {n for n, _ in _pt_env_names(
        'import os\nos.environ.get("PT_A", "1")\nos.getenv("PT_B")\n'
        'os.environ["PT_C"]\n"PT_D" in os.environ\n"""PT_E in prose"""'
    )} == {"PT_A", "PT_B", "PT_C", "PT_D"}


# ----------------------------------------------- metrics-reference coverage
# The generated metrics reference (``python -m paddle_tpu.observability``)
# renders whatever _INSTRUMENT_MODULES imports — a module that registers
# instruments but is missing from that tuple silently drops its metrics
# from the reference. Modules whose registrations are intentionally
# off-reference go in the allowlist with a reason.
_REFERENCE_ALLOWLIST = {
    # e.g. "paddle_tpu/some/module.py": "registers per-test scratch names",
}


def test_instrument_registering_modules_are_in_the_reference():
    from paddle_tpu.observability.__main__ import _INSTRUMENT_MODULES
    root = pathlib.Path(__file__).resolve().parent.parent
    pkg = root / "paddle_tpu"
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        rel = str(path.relative_to(root))
        if rel in _REFERENCE_ALLOWLIST:
            continue
        if not re.search(r"\bMETRICS\.(counter|gauge|histogram)\s*\(",
                         path.read_text()):
            continue
        mod = ".".join(path.relative_to(root).with_suffix("").parts)
        if mod.endswith(".__init__"):
            mod = mod[:-len(".__init__")]
        if mod not in _INSTRUMENT_MODULES:
            offenders.append(f"{rel}: registers instruments but {mod!r} "
                             "is not in observability.__main__."
                             "_INSTRUMENT_MODULES")
    assert not offenders, (
        "modules whose instruments the generated metrics reference would "
        "silently omit (add them to _INSTRUMENT_MODULES or allowlist with "
        "a reason):\n" + "\n".join(offenders))


def test_pipeline_divergent_handoff_flagged():
    """A stage that only hands off inside one cond branch deadlocks —
    the lint catches it before it reaches hardware."""
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.utils.lint import lint_collectives

    def bad_stage(x):
        return lax.cond(
            x.sum() > 0,
            lambda v: lax.ppermute(v, "pp", [(0, 1), (1, 2), (2, 3), (3, 0)]),
            lambda v: v,
            x)

    rep = lint_collectives(bad_stage, jnp.ones((2, 2)), axis_env=[("pp", 4)])
    assert not rep.ok
    assert any(i.kind == "cond-divergence" for i in rep.issues)


def test_importing_the_package_claims_no_device():
    """Importing paddle_tpu, the paged model file or the serving package
    must not initialise a JAX backend: a launcher that merely imports
    them would otherwise hold the chip (the async tick's donation used
    to ask ``jax.default_backend()`` while the module was imported)."""
    import subprocess
    import sys
    code = ("import jax, paddle_tpu, paddle_tpu.models.paged, "
            "paddle_tpu.serving\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "print('no backend')")
    root = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "no backend" in r.stdout, r.stderr[-2000:]
