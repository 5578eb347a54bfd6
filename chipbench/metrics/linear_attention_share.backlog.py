"""From the trace: device seconds of the delta-rule chunk kernel, under the
name its ``pallas_call`` gives it, over device busy seconds (the rule's
decode step is XLA's own form and has no name of its own in a trace). None
where the trace holds no such kernel."""
import _spans

UNIT = "%"


def read(run):
    return _spans.kernel_share(run, "gated_delta_chunk")
