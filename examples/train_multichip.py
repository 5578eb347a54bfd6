"""Hybrid-parallel LLaMA training on a device mesh (dp x fsdp x tp).

Runs on the attached chips, and fails if there are fewer than --devices.
The CPU dry run on virtual devices is an explicit mode:

    python examples/train_multichip.py --cpu-dryrun --devices 8 --steps 3
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--fsdp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--ep", type=int, default=2,
                    help="expert-parallel width for the MoE loss-equality "
                         "leg (0/1 skips it)")
    ap.add_argument("--cpu-dryrun", action="store_true",
                    help="run on --devices virtual CPU devices instead of "
                         "the attached chips")
    args = ap.parse_args()

    if args.cpu_dryrun:
        # both must be in place BEFORE the backend initialises (the first
        # jax.devices() call)
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={args.devices}"
    import jax
    if args.cpu_dryrun:
        jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < args.devices:
        raise SystemExit(
            f"train_multichip: asked for {args.devices} devices, found "
            f"{len(jax.devices())} ({jax.default_backend()}); pass "
            "--cpu-dryrun for a virtual CPU mesh")
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()

    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.train import make_train_step
    from paddle_tpu.train.step import init_state

    mesh = HybridMesh(dp=args.dp, fsdp=args.fsdp, tp=args.tp,
                      devices=jax.devices()[:args.devices])
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=64,
                           num_attention_heads=4, num_key_value_heads=2)
    batch = args.dp * args.fsdp * 2
    rs = np.random.RandomState(0)

    with mesh:
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-3,
                              grad_clip=opt.ClipGradByGlobalNorm(1.0))
        state = init_state(model, optimizer, mesh)
        step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, mesh)
        for i in range(args.steps):
            ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, 16)))
            labels = jnp.concatenate(
                [ids[:, 1:], -100 * jnp.ones((batch, 1), ids.dtype)], axis=1)
            ids = jax.device_put(ids, mesh.batch_sharding())
            labels = jax.device_put(labels, mesh.batch_sharding())
            state, loss = step(state, ids, labels)
            print(f"step {i} loss {float(loss):.4f} "
                  f"(mesh dp={args.dp} fsdp={args.fsdp} tp={args.tp})")

    if args.ep > 1:
        # expert-parallel leg: the MoE loss under an ep mesh (experts
        # sharded, tokens all-to-all'd through the grouped GEMM) must
        # equal the single-device loss on the same batch
        from paddle_tpu.models.moe_llm import MoEConfig, MoEForCausalLM
        pt.seed(0)
        moe_cfg = MoEConfig(base=cfg, num_experts=4, top_k=2,
                            capacity_factor=None, moe_every=1)
        moe = MoEForCausalLM(moe_cfg)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 16)))
        labels = jnp.concatenate(
            [ids[:, 1:], -100 * jnp.ones((2, 1), ids.dtype)], axis=1)
        ref = float(moe.loss(ids, labels))
        ep_mesh = HybridMesh(ep=args.ep, devices=jax.devices()[:args.ep])
        with ep_mesh:
            ep_loss = float(moe.loss(ids, labels))
        print(f"moe loss single={ref:.6f} ep{args.ep}={ep_loss:.6f}")
        np.testing.assert_allclose(ep_loss, ref, rtol=2e-5)
    return float(loss)


if __name__ == "__main__":
    main()
