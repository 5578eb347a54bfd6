"""The share of the chip's bf16 peak the whole traced window reached, for a
model with window layers beside full ones and expert layers that hold
every expert: forward FLOPs of every token the window computed
(``window_moe.py``: a window layer's keys counted up to the window, a full
layer's to the causal edge, padding not counted, the routed experts by the
spans' ``routed_pairs``, the head for the rows that went through it) over
the window's seconds over the published peak. Prompt tokens as the
``exe.prefill*`` spans count them (a call's ``useful`` tokens at offset
``ctx_tokens`` - ``useful``: exact for one live row a call, whose last
position alone goes through the head), decoded tokens at the blocks each
space's layers walked (``kv_blocks_window`` / ``kv_blocks_full`` x the block
keys, a window layer's at most ``slots`` x the window). None where no span
carries ``routed_pairs`` beside ``kv_blocks_window``."""
import _spans
from chipbench import hybrid, window_moe

UNIT = "%"


def read(run):
    t = run.get("trace")
    events = _spans.program_events()
    routed = window_moe.routed_calls(events)
    if (not t or not t.get("window_s") or not routed
            or not window_moe.space_ticks(events)):
        return None
    cfg = run["config"]
    block, window = run["cell"]["engine"]["block_size"], cfg["sliding_window"]
    tokens = head_rows = keys_w = keys_f = 0.0
    for e in events:
        a = e.get("args", {})
        if e["name"].startswith("exe.prefill") and "ctx_tokens" in a:
            n, off = a["useful"], a["ctx_tokens"] - a["useful"]
            tokens += n
            head_rows += 1
            keys_w += window_moe.window_keys(off, n, window)
            keys_f += window_moe.causal_keys(off, n)
        elif e["name"] == "serving.decode" and "kv_blocks_window" in a:
            tokens += a["slots"]
            head_rows += a["slots"]
            keys_w += min(a["kv_blocks_window"] * block, a["slots"] * window)
            keys_f += a["kv_blocks_full"] * block
    flops = window_moe.forward_flops(cfg, tokens, head_rows, keys_w, keys_f,
                                     sum(p for p, _ in routed))
    return (100.0 * flops / t["window_s"]
            / hybrid.peaks(run)["bf16_flops_per_s"], len(routed))
