"""Output tokens stamped inside the window, over the window's seconds."""
UNIT = "tokens/s"


def read(run):
    return run["tokens_in_window"] / run["seconds"], run["tokens_in_window"]
