"""conv_ms_per_round (ms): device time of the convolution operations per
round, averaged over the chips, classified by the HLO category the trace
carries with each operation.  Layer: site update."""
from fedbench import tracing


def is_conv(op):
    return "convolution" in op.category


def read(r):
    if r.rounds == 0 or not any(op.category for c in r.trace.chips
                                for op in r.trace.ops[c]):
        return None
    total = sum(tracing.op_time_ns(r.trace, c, is_conv)[0]
                for c in r.trace.chips) / len(r.trace.chips)
    return total / 1e6 / r.rounds
