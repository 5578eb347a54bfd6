"""Of the prompt tokens the window took in, the share the radix cache served:
``mgr.cache_stats`` token hits over those hits plus the prompt tokens sent
through the two prefill programs (the driver's own count), both taken
between the window's marks."""
UNIT = "%"


def read(run):
    hits = run["cache"].get("token_hits", 0)
    prefilled = sum(n for _, _, n in run["prefill_calls"])
    if not hits + prefilled:
        return None
    return 100.0 * hits / (hits + prefilled), hits + prefilled
