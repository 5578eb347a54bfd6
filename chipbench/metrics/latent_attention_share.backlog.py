"""From the trace: device seconds of the two latent attention kernels
(``paged_latent_chunk_attention`` of the prefill programs,
``paged_latent_decode_attention`` of the tick), under the names their
``pallas_call`` gives them, over device busy seconds. None where the trace
holds neither."""
import _spans

UNIT = "%"
KERNELS = ("paged_latent_chunk_attention", "paged_latent_decode_attention")


def read(run):
    shares = [_spans.kernel_share(run, k) for k in KERNELS]
    if all(s is None for s in shares):
        return None
    return sum(s for s in shares if s is not None)
