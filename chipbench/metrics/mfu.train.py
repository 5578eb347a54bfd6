"""Model FLOP/s utilisation: the FLOPs the mathematics requires per token
(``chipbench/flops.py``: causal half, nothing recomputed) times tokens per
second per chip, over the chip's published bf16 peak."""
from chipbench import flops

UNIT = "%"


def read(run):
    if not run["steps"]:
        return None
    rate = len(run["steps"]) * run["tokens_per_step"] / run["seconds"] / run["chips"]
    need = flops.train_flops_per_token(run["config"], run["seq_len"])
    return 100.0 * need * rate / flops.peaks(run["device_kind"])["bf16_flops_per_s"]
