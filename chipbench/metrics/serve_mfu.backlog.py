"""The share of the chip's bf16 peak the whole traced window reached:
forward FLOPs of every token the window computed (``hybrid.py``'s count,
padding not counted) over the window's seconds over the published peak.
Prompt tokens sent to a prefill program at their contexts: a call's
``useful`` tokens L at offset O = ``ctx_tokens`` - L attend L O + L (L + 1)
/ 2 keys in all (exact for one live row a call, which is what a cell with
``max_prompt_len`` >= 256 sends). Decoded tokens at theirs: ``slots``
tokens over ``kv_blocks`` x the block keys. None where the spans carry no
``ctx_tokens``: the program is not a hybrid's."""
import _spans
from chipbench import hybrid

UNIT = "%"


def read(run):
    t = run.get("trace")
    events = _spans.program_events()
    calls = [e["args"] for e in events if e["name"].startswith("exe.prefill")
             and "ctx_tokens" in e["args"]]
    if not t or not t.get("window_s") or not calls:
        return None
    cfg = run["config"]
    per_token = hybrid.forward_flops_per_token(cfg, 0.0)
    per_key = hybrid.forward_flops_per_token(cfg, 1.0) - per_token
    tokens = keys = 0.0
    for a in calls:
        n, off = a["useful"], a["ctx_tokens"] - a["useful"]
        tokens += n
        keys += n * off + n * (n + 1) / 2.0
    block = run["cell"]["engine"]["block_size"]
    ticks = [e["args"] for e in events if e["name"] == "serving.decode"]
    for a in ticks:
        tokens += a["slots"]
        keys += a["kv_blocks"] * block
    flops = tokens * per_token + keys * per_key
    return (100.0 * flops / t["window_s"]
            / hybrid.peaks(run)["bf16_flops_per_s"], len(calls) + len(ticks))
