"""Tokens in the steps that end inside the window, over the window's
seconds and the chips."""
UNIT = "tokens/s/chip"


def read(run):
    n = len(run["steps"])
    return (n * run["tokens_per_step"] / run["seconds"] / run["chips"], n)
