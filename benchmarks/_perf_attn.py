"""Attention microbench: pallas flash vs xla attention, fwd and fwd+bwd.

NB: q/k/v must be ARGUMENTS of the jitted fns — closed-over arrays become
HLO constants baked into the compiled program.
"""
import os, sys, time, json
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas.flash_attention import flash_attention

B, S, H, D = 4, 2048, 16, 128
rs = np.random.RandomState(0)
q = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
k = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
v = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)

def timeit(f, *a, n=20):
    r = f(*a); float(jax.device_get(jnp.sum(r.astype(jnp.float32))))
    r = f(*a); float(jax.device_get(jnp.sum(r.astype(jnp.float32))))
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*a)
    float(jax.device_get(jnp.sum(r.astype(jnp.float32))))
    return (time.perf_counter() - t0) / n

flash_f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
xla_f = jax.jit(lambda q, k, v: A.xla_attention(q, k, v, is_causal=True))
t_flash = timeit(flash_f, q, k, v)
print("flash fwd", t_flash, flush=True)
t_xla = timeit(xla_f, q, k, v)
print("xla fwd", t_xla, flush=True)

g_flash = jax.jit(jax.grad(
    lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))))
g_xla = jax.jit(jax.grad(
    lambda q, k, v: jnp.sum(A.xla_attention(q, k, v, is_causal=True).astype(jnp.float32))))
t_gflash = timeit(g_flash, q, k, v)
print("flash bwd", t_gflash, flush=True)
t_gxla = timeit(g_xla, q, k, v)

flops = 2 * 2 * B * H * S * S * D * 0.5
print(json.dumps({
    "flash_fwd_ms": round(t_flash*1e3,2), "xla_fwd_ms": round(t_xla*1e3,2),
    "flash_fwdbwd_ms": round(t_gflash*1e3,2), "xla_fwdbwd_ms": round(t_gxla*1e3,2),
    "flash_fwd_tflops": round(flops/t_flash/1e12,1),
    "xla_fwd_tflops": round(flops/t_xla/1e12,1),
}))
