"""Mean share of the engine's slots active after each tick of the window."""
import numpy as np

UNIT = "%"


def read(run):
    if not run["ticks"]:
        return None
    active = [t[2] for t in run["ticks"]]
    return 100.0 * float(np.mean(active)) / run["num_slots"], len(active)
