"""The recycling's guard: blocks held in the window space over what the
same rows would hold there were the window layers global, which is what
they hold in the full space (both tables are indexed by position), in
percent: the median over the traced ``serving.gauges`` spans of
``window_held`` over ``full_held``. Near 100 the window space is not
recycled. None where no sweep counts the two spaces."""
import _lib
import _spans

UNIT = "%"


def read(run):
    return _lib.percentile(
        [100.0 * e["args"]["window_held"] / e["args"]["full_held"]
         for e in _spans.program_events()
         if e["name"] == "serving.gauges"
         and e["args"].get("full_held", 0) > 0], 50)
