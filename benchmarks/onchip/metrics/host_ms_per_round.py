"""host_ms_per_round (ms): host time the round driver spends per round
in the window while the chunk program is not executing — each
``repro.chunk`` span's duration less its ``repro.chunk.execute`` child —
summed over the window's chunks and divided by its rounds.  The chunk the
harness ends (an exception closes its spans) counts without its
``repro.chunk.record`` child, which holds the harness's own work of
closing the window.  Layer: round driver."""
from fedbench import program_spans


def read(r):
    chunks, children = program_spans.window_chunks(r)
    if r.rounds == 0 or not chunks:
        return None
    host_ns = 0
    for c in chunks:
        for s in children[c.id]:
            if s.name == "repro.chunk.execute" or (
                    c.error and s.name == "repro.chunk.record"):
                host_ns -= s.end_ns - s.start_ns
        host_ns += c.end_ns - c.start_ns
    return host_ns / 1e6 / r.rounds
