"""From the trace: device seconds of the paged decode-attention kernel,
under the name its ``pallas_call`` gives it, over device busy seconds."""
import _spans

UNIT = "%"


def read(run):
    return _spans.kernel_share(run, "paged_decode_attention")
