"""Serve any local HF checkpoint: auto registry -> continuous-batching
engine with mixed greedy + beam traffic, or family-agnostic generation.

    python examples/serve_auto.py /path/to/hf_checkpoint_dir

(ref: PaddleNLP `llm` predictor entrypoint + AutoModelForCausalLM.)
"""
import sys

import numpy as np

from paddle_tpu.models.auto import auto_from_pretrained
from paddle_tpu.models.decoding import generic_generate
from paddle_tpu.serving import LLMEngine, Request


def main(ckpt_dir):
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()
    model = auto_from_pretrained(ckpt_dir)
    prompts = [np.arange(3, 11), np.arange(5, 12), np.arange(2, 8)]

    if type(model).__name__ == "LlamaForCausalLM" or hasattr(model, "model"):
        # llama-family: the paged continuous-batching engine (fast path)
        eng = LLMEngine(model, num_slots=2, block_size=16,
                        max_prompt_len=32, max_seq_len=64)
        for p in prompts[:2]:
            eng.generate(p, max_new_tokens=12,
                         stream=lambda r, t: print(f"req {r.req_id} -> {t}"))
        eng.generate(prompts[2], max_new_tokens=12, num_beams=2)  # beams
        out = eng.run()
        for rid, toks in sorted(out.items()):
            print(f"req {rid}: {toks}")
    else:
        # any other causal family: generic full-forward decoding
        out = generic_generate(model, np.stack([prompts[0]]),
                               max_new_tokens=12)
        print(np.asarray(out))


if __name__ == "__main__":
    main(sys.argv[1])
