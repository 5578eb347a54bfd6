"""The paged decode-attention kernel's share of its roofline, which is its
bytes (the kernel reads every live K/V block once and computes little):
over the traced ``serving.decode`` spans, ``kv_blocks`` (pool blocks a cache
layer the tick's running slots hold) x the block's tokens x the K and V
bytes of a token in one cache layer x the cache layers (``looped.py``; the
span's own ``cache_layers`` where the program gives it), over the seconds
of ``paged_decode_attention`` in the reduced trace, over the chip's
published HBM bytes a second."""
import _spans

from chipbench import looped

UNIT = "%"
KERNEL = "paged_decode_attention"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    seconds = next((s for name, s in t["device_ops"]
                    if name.lstrip("%") == KERNEL), None)
    ticks = [e["args"] for e in _spans.program_events()
             if e["name"] == "serving.decode" and "kv_blocks" in e["args"]]
    if not seconds or not ticks:
        return None
    cfg = run["config"]
    per_block_layer = (run["cell"]["engine"]["block_size"]
                       * looped.kv_bytes_per_token_layer(cfg))
    read_bytes = sum(a["kv_blocks"] * per_block_layer
                     * a.get("cache_layers", looped.cache_layers(cfg))
                     for a in ticks)
    return (100.0 * read_bytes / seconds / looped.hbm_bytes_per_s(run),
            len(ticks))
