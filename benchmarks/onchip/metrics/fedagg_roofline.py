"""fedagg_roofline (%): the least time the Eq. 1 fold's bytes need at the
chip's HBM bandwidth over the Pallas fold kernel's device time per call.
The bytes are the [S, N] rows read, the [S] weights read and the [N]
global written, with N the model's own size.  Layer: aggregation kernels."""
from fedbench import counts, tracing


def is_fold(op):
    return op.hlo_name.lstrip("%").startswith("fedagg")


def read(r):
    chip = r.trace.chips[0]
    t, calls = tracing.op_time_ns(r.trace, chip, is_fold)
    if calls == 0:
        return None
    least = (counts.fold_bytes(r.num_params, r.cell.traffic["sites"])
             / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t / calls / 1e9)
