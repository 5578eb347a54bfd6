"""The latent decode kernel's share of its roofline, which is its bytes:
over the traced ``serving.decode`` spans, ``kv_blocks`` x the block's
tokens x the model's latent row (``latent_moe.py``: the latent and the
shared rotated key, whatever a pool pads a row to) x ``cache_layers``,
over the seconds of ``paged_latent_decode_attention`` in the reduced
trace, over the chip's published HBM bytes a second. None where decode has
no kernel of that name in the trace."""
import _spans
from chipbench import hybrid, latent_moe

UNIT = "%"
KERNEL = "paged_latent_decode_attention"


def read(run):
    seconds = hybrid.kernel_seconds(run, KERNEL)
    ticks = [e["args"] for e in _spans.program_events()
             if e["name"] == "serving.decode" and "kv_blocks" in e["args"]
             and "cache_layers" in e["args"]]
    if not seconds or not ticks:
        return None
    per_block_layer = (run["cell"]["engine"]["block_size"]
                       * latent_moe.cache_bytes_per_token_layer(run["config"]))
    read_bytes = sum(a["kv_blocks"] * a["cache_layers"] for a in ticks) \
        * per_block_layer
    return (100.0 * read_bytes / seconds
            / hybrid.peaks(run)["hbm_bytes_per_s"], len(ticks))
