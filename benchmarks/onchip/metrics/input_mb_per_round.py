"""input_mb_per_round (MB): bytes the round driver puts on the device per
round in the window, the ``bytes`` of each ``repro.chunk.inputs`` span
(batches, masks, index arrays) summed over the window's chunks, over its
rounds, in 1e6 bytes.  Layer: round driver."""
from fedbench import program_spans


def read(r):
    chunks, children = program_spans.window_chunks(r)
    got = [s.attrs["bytes"] for c in chunks for s in children[c.id]
           if s.name == "repro.chunk.inputs" and "bytes" in s.attrs]
    if r.rounds == 0 or not got:
        return None
    return sum(got) / r.rounds / 1e6
