"""Measure the serving engine's host-vs-device split at B=64 (VERDICT r2
item 6: host bookkeeping must be <10% of the decode tick).

Runs a 64-slot engine on a small-but-real model, fills every slot, decodes
a fixed number of ticks, and prints one JSON line with the split. On CPU
the "device" time is the jitted tick itself plus the [B] token fetch.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import LLMEngine, Request

    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, hidden_size=128,
                           num_attention_heads=8, num_key_value_heads=4,
                           intermediate_size=256, vocab_size=1024)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)

    slots = 64
    new_tokens = 48
    eng = LLMEngine(model, num_slots=slots, block_size=16,
                    max_prompt_len=64, max_seq_len=128)
    for _ in range(slots):
        n = int(rs.randint(8, 64))
        eng.add_request(Request(rs.randint(0, 1024, (n,)),
                                max_new_tokens=new_tokens))
    # admission tick (compiles prefill+tick); exclude from the measurement
    eng.step()
    eng.step()
    eng.stats = {"host_s": 0.0, "device_s": 0.0, "ticks": 0}
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
    wall = time.perf_counter() - t0
    s = eng.stats
    host_frac = s["host_s"] / max(s["host_s"] + s["device_s"], 1e-9)
    print(json.dumps({
        "metric": "serving host fraction of decode tick (B=64)",
        "value": round(host_frac, 4), "unit": "fraction",
        "extra": {"ticks": s["ticks"],
                  "host_ms_per_tick": round(1e3 * s["host_s"] / s["ticks"], 3),
                  "device_ms_per_tick": round(1e3 * s["device_s"] / s["ticks"], 3),
                  "wall_s": round(wall, 2),
                  "device": str(jax.devices()[0]),
                  "target": "< 0.10"}}))


if __name__ == "__main__":
    main()
