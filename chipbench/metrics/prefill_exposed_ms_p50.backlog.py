"""Per tick that ran a prefill program, the sum of its starved intervals
(before its first prefill dispatch; between ``exe.sample``'s end and the
decode dispatch); the median over such ticks, the split by span in the note
line."""
import _exposed
import _spans

UNIT = "ms"


def read(run):
    return _exposed.tick_reading(_spans.program_events(), prefill=True)
