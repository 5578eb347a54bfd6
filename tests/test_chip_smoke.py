"""chip_smoke.py and the rules it stands for, on the CPU: the ``--tiny``
rehearsal passes without ever printing the chip's result line, the real
run refuses to start without a TPU, and a kernel that raises is the
caller's error on every dispatch path (no quiet XLA fallback)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(*argv, **env):
    # the checkout's own HOME and TMPDIR, and nothing else of the parent's
    base = {k: os.environ[k] for k in ("HOME", "TMPDIR") if k in os.environ}
    base.update(PATH="/usr/bin:/bin", JAX_PLATFORMS="cpu",
                PYTHONUNBUFFERED="1")
    return subprocess.run([sys.executable, *argv], cwd=REPO,
                          env={**base, **env}, capture_output=True,
                          text=True, timeout=600)


def test_tiny_rehearsal_passes_and_never_prints_the_chip_line(tmp_path):
    cache = tmp_path / "cache"
    r = _run("chip_smoke.py", "--tiny", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    last = lines[-1]
    assert last == {"rehearsal": "passed", "tiny": True, "platform": "cpu"}
    assert '"ok": true' not in r.stdout and '"platform": "tpu"' not in r.stdout
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert phases["serve"]["tokens_served"] == 108
    assert phases["serve"]["radix_token_hits"] >= 96
    assert phases["train"]["losses"][-1] < phases["train"]["losses"][0]
    # the cache went where the environment said, and nowhere else was set
    assert lines[0]["compile_cache_dir"] == str(cache)
    assert lines[-2]["cache_entries_after"] == len(os.listdir(cache)) > 0


def test_real_run_without_a_tpu_fails_and_prints_no_result():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_compile_cache_default_is_a_fixed_path_in_the_checkout():
    r = _run("-c", "from paddle_tpu.core.device import "
             "enable_compilation_cache as e; import jax; print(e()); "
             "print(jax.config.jax_compilation_cache_dir)")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_set_device_raises_for_a_platform_that_is_not_there():
    import paddle_tpu as pt
    pt.set_device("cpu")
    with pytest.raises(RuntimeError):
        pt.set_device("tpu")


def _boom(*a, **k):
    raise RuntimeError("mosaic says no")


def _sdpa(monkeypatch):
    from paddle_tpu.ops import scaled_dot_product_attention
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention", _boom)
    q = jnp.ones((1, 128, 2, 128), jnp.float32)
    return lambda: scaled_dot_product_attention(q, q, q, is_causal=True)


def _rms(monkeypatch):
    from paddle_tpu.ops import fused_rms_norm
    from paddle_tpu.ops.pallas import norms
    monkeypatch.setattr(norms, "rms_norm", _boom)
    return lambda: fused_rms_norm(jnp.ones((4, 512)), jnp.ones((512,)))


def _paged(kernel):
    def build(monkeypatch):
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(pa, f"paged_{kernel}_attention_pallas", _boom)
        # head_dim 128: a slab Mosaic can copy, so the decode dispatcher
        # takes its kernel on a TPU
        pool = jnp.ones((4, 8, 2, 128), jnp.float32)
        tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        lens = jnp.asarray([10, 13], jnp.int32)
        if kernel == "decode":
            q = jnp.ones((2, 4, 128), jnp.float32)
            return lambda: pa.paged_decode_attention(q, pool, pool, tables,
                                                     lens)
        q = jnp.ones((2, 4, 4, 128), jnp.float32)
        return lambda: pa.paged_chunk_attention(
            q, pool, pool, tables, lens - 4, jnp.asarray([4, 3], jnp.int32))
    return build


@pytest.mark.parametrize("build", [_sdpa, _rms, _paged("decode"),
                                   _paged("chunk")],
                         ids=["scaled_dot_product_attention",
                              "fused_rms_norm", "paged_decode_attention",
                              "paged_chunk_attention"])
def test_a_raising_kernel_surfaces_from_its_dispatcher(monkeypatch, build):
    call = build(monkeypatch)
    assert np.isfinite(np.asarray(call())).all()    # off-TPU: the XLA path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="mosaic says no"):
        call()


def test_mosaic_kernels_apply_only_where_xla_does_not_partition(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: the dispatchers
    take them on a TPU outside any multi-device mesh and inside a
    shard_map whose axes are all manual, never under a HybridMesh whose
    sharded axes XLA partitions itself."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.ops.pallas import mosaic_kernels_apply
    assert not mosaic_kernels_apply()                     # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mosaic_kernels_apply()
    seen = []

    def body(x):
        seen.append(mosaic_kernels_apply())
        return x

    with HybridMesh(devices=jax.devices()[:1]):
        assert mosaic_kernels_apply()                     # one device
    mesh = HybridMesh(fsdp=2, tp=2, devices=jax.devices()[:4])
    with mesh:
        assert not mosaic_kernels_apply()
        jax.shard_map(body, mesh=mesh.mesh, in_specs=P("fsdp"),
                      out_specs=P("fsdp"))(jnp.ones(4))
        jax.shard_map(body, mesh=mesh.mesh, in_specs=P("fsdp"),
                      out_specs=P("fsdp"), axis_names={"fsdp"})(jnp.ones(4))
    assert seen == [True, False]      # all axes manual; tp left to XLA
