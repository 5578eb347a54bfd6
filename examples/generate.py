"""Text generation with the KV-cache decode loop.

Greedy / top-k / top-p sampling and beam search on any of the decoder
models (LLaMA / Mistral / Qwen2) — one compiled while_loop, pre-allocated
cache, no per-step recompiles.

    python examples/generate.py --model mistral --strategy top_p
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# device choice is explicit; default CPU so the example runs anywhere
_ON_TPU = "--device=tpu" in sys.argv or (
    "--device" in sys.argv
    and sys.argv[sys.argv.index("--device") + 1:][:1] == ["tpu"])
if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["llama", "mistral", "qwen2"],
                    default="llama")
    ap.add_argument("--strategy", choices=["greedy", "top_k", "top_p", "beam"],
                    default="greedy")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--device", choices=["cpu", "tpu"], default="cpu")
    args = ap.parse_args()
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()

    pt.seed(0)
    if args.model == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(LlamaConfig.tiny()).eval()
    elif args.model == "mistral":
        from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM
        model = MistralForCausalLM(MistralConfig.tiny()).eval()
    else:
        from paddle_tpu.models.qwen import Qwen2Config, Qwen2ForCausalLM
        model = Qwen2ForCausalLM(Qwen2Config.tiny()).eval()

    rs = np.random.RandomState(0)
    prompt = jnp.asarray(rs.randint(0, model.cfg.vocab_size, (1, 8)))

    if args.strategy == "beam":
        from paddle_tpu.models.decoding import beam_search
        out, scores = beam_search(model, prompt, num_beams=4,
                                  max_new_tokens=args.max_new_tokens)
        print("beam score:", float(scores[0]))
    else:
        from paddle_tpu.models.decoding import generate
        kw = {"greedy": dict(temperature=0.0),
              "top_k": dict(temperature=0.8, top_k=50),
              "top_p": dict(temperature=0.8, top_p=0.9)}[args.strategy]
        out = generate(model, prompt, max_new_tokens=args.max_new_tokens,
                       rng=jax.random.PRNGKey(0), **kw)
    print(f"{args.model}/{args.strategy}:", np.asarray(out)[0].tolist())


if __name__ == "__main__":
    main()
