"""From the ``rows`` and ``useful`` counts the executor puts on its
``exe.prefill`` and ``exe.prefill_chunk`` spans: token-rows sent that
carried no prompt token, over all token-rows sent."""
import _spans

UNIT = "%"


def read(run):
    return _spans.pad_row_share(_spans.program_events())
