"""How much of the held experts' weights a decode tick streams: the median
over the traced ``serving.decode`` spans of ``experts_hit`` (held experts
that got at least one token, summed over the expert layers) over the
experts held x the expert layers, in percent. The cell's guard that the
live set of experts does not move with the seed. None where the spans
carry no ``experts_hit``."""
import _lib
import _spans
from chipbench import latent_moe

UNIT = "%"


def read(run):
    cfg = run["config"]
    held = cfg["n_routed_experts"] * latent_moe.expert_layers(cfg)
    return _lib.percentile(
        [100.0 * e["args"]["experts_hit"] / held
         for e in _spans.program_events()
         if e["name"] == "serving.decode" and "experts_hit" in e["args"]], 50)
