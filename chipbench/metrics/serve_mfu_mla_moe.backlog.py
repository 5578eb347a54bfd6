"""The share of the chip's bf16 peak the whole traced window reached, for a
model with latent attention and held experts: forward FLOPs of every token
the window computed at its context (``latent_moe.py``: the expanded form's
count, padding not counted, the routed experts by the spans'
``routed_pairs``) over the window's seconds over the published peak.
Prompt tokens at their contexts as ``serve_mfu.backlog`` counts them (a
call's ``useful`` tokens L at offset ``ctx_tokens`` - L attend L O + L (L +
1) / 2 keys: exact for one live row a call), decoded tokens at ``kv_blocks``
x the block keys. None where no span carries ``routed_pairs``."""
import _spans
from chipbench import hybrid, latent_moe

UNIT = "%"


def read(run):
    t = run.get("trace")
    events = _spans.program_events()
    routed = latent_moe.routed_calls(events)
    if not t or not t.get("window_s") or not routed:
        return None
    cfg = run["config"]
    tokens = keys = 0.0
    for e in events:
        a = e.get("args", {})
        if e["name"].startswith("exe.prefill") and "ctx_tokens" in a:
            n, off = a["useful"], a["ctx_tokens"] - a["useful"]
            tokens += n
            keys += n * off + n * (n + 1) / 2.0
        elif e["name"] == "serving.decode" and "kv_blocks" in a:
            tokens += a["slots"]
            keys += a["kv_blocks"] * run["cell"]["engine"]["block_size"]
    flops = latent_moe.forward_flops(cfg, tokens, keys,
                                     sum(p for p, _ in routed))
    return (100.0 * flops / t["window_s"]
            / hybrid.peaks(run)["bf16_flops_per_s"], len(routed))
