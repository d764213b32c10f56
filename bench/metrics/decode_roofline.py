"""Roofline share of the decode sub-steps: the least time the chip needs
for a traced decode block's work (bench/counts/ of the configuration's
family; for `dense_lm` weights once per sub-step at bfloat16, plus the
live keys and values of the rows that decode, against the peaks of
bench/peaks.json), over the device time of one decode-block program, both
as means over the traced window. Blocks count on the host when their step
ran wholly inside the trace, programs on the device when they start inside
it, so the two sets can differ by a block at the window's edges; means
keep that from moving the share."""
from bench.readers import DECODE, module


def read(ctx):
    hit = module(ctx, DECODE)
    if not hit or not hit[0] or not ctx["traced_blocks"]:
        return None
    least = ctx["traced_least_s"] / ctx["traced_blocks"]
    return 100.0 * least / (hit[1] / hit[0])
