"""conv_ms_per_round (ms): device time of the convolution operations per
round, averaged over the chips, classified by the HLO category the trace
carries with each operation.  A trace with no convolution gives no value.
Layer: site update."""
from fedbench import tracing


def is_conv(op):
    return "convolution" in op.category


def read(r):
    per_chip = [tracing.op_time_ns(r.trace, c, is_conv)
                for c in r.trace.chips]
    if r.rounds == 0 or not any(calls for _, calls in per_chip):
        return None
    return sum(t for t, _ in per_chip) / len(per_chip) / 1e6 / r.rounds
