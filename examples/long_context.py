"""Long-context training with sequence parallelism.

Shows the two context-parallel modes on the flagship model:
  * ring:    KV blocks rotate over ICI (ppermute); best when S/chip is big
  * ulysses: all_to_all seq<->head re-sharding; best when heads >= sp

Runs on the CPU virtual mesh by default (8 devices); the same code scales
to a TPU slice — only the mesh shape changes.

    python examples/long_context.py --mode ring --seq 512
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# device choice is explicit (--device tpu to run on a slice); the default
# is the 8-device CPU virtual mesh so the example runs anywhere
_ON_TPU = "--device=tpu" in sys.argv or (
    "--device" in sys.argv
    and sys.argv[sys.argv.index("--device") + 1:][:1] == ["tpu"])
if not _ON_TPU:
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import HybridMesh
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.train import make_train_step
from paddle_tpu.train.step import init_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["ring", "ulysses"], default="ring")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--sp", type=int, default=4)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--device", choices=["cpu", "tpu"], default="cpu")
    args = ap.parse_args()
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()

    pt.seed(0)
    cfg = LlamaConfig.tiny(
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4 if args.mode == "ulysses" else 2,
        max_position_embeddings=args.seq,
        sequence_parallel=args.mode)
    mesh = HybridMesh(dp=args.dp, sp=args.sp,
                      devices=jax.devices()[:args.dp * args.sp])
    print(f"mesh dp={args.dp} sp={args.sp}, mode={args.mode}, S={args.seq}")

    with mesh:
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-3)
        state = init_state(model, optimizer, mesh)
        rs = np.random.RandomState(0)
        ids = jax.device_put(
            jnp.asarray(rs.randint(0, cfg.vocab_size, (args.dp * 2, args.seq))),
            mesh.batch_sharding())
        labels = jnp.concatenate(
            [ids[:, 1:], -100 * jnp.ones((ids.shape[0], 1), ids.dtype)], axis=1)
        labels = jax.device_put(labels, mesh.batch_sharding())
        step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, mesh)
        for i in range(args.steps):
            state, loss = step(state, ids, labels)
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
