"""How much of the experts' weights a decode tick streams, for a model
whose expert layers hold every expert: the median over the traced
``serving.decode`` spans of ``experts_hit`` (experts that got at least one
token, summed over the expert layers) over ``num_experts`` x the expert
layers, in percent. The cell's guard that the live set of experts does not
move with the seed. None where the spans carry no ``experts_hit``."""
import _lib
import _spans
from chipbench import window_moe

UNIT = "%"


def read(run):
    cfg = run["config"]
    if "num_experts" not in cfg:
        return None
    held = cfg["num_experts"] * window_moe.expert_layers(cfg)
    return _lib.percentile(
        [100.0 * e["args"]["experts_hit"] / held
         for e in _spans.program_events()
         if e["name"] == "serving.decode" and "experts_hit" in e["args"]], 50)
