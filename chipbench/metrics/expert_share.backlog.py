"""From the trace: device seconds of the grouped expert products
(``grouped_matmul``, both of an expert layer's) over device busy seconds.
None where the trace holds no such kernel."""
import _spans

UNIT = "%"


def read(run):
    return _spans.kernel_share(run, "grouped_matmul")
