"""chunk_lower_s (s): the ``repro.chunk.lower`` span of the chunk program
the window runs: tracing the program and lowering it to the compiler's
input, inside set-up.  Layer: chunk program."""
from fedbench import program_spans


def read(r):
    s = program_spans.program_span(r, "repro.chunk.lower")
    return None if s is None else s.seconds
