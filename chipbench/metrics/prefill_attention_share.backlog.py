"""From the trace: device seconds of the paged chunk-attention kernel (the
chunked-prefill forward's), under the name its ``pallas_call`` gives it,
over device busy seconds."""
import _spans

UNIT = "%"


def read(run):
    return _spans.kernel_share(run, "paged_chunk_attention")
