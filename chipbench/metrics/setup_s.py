"""Process start to the start of the window: imports, weights, compiling or
reading the compile cache, warm-up and the traffic's lead-in."""
UNIT = "s"


def read(run):
    return run["setup_s"]
