"""chunk_compile_s (s): the ``repro.chunk.compile`` span of the chunk
program the window runs: a compile, or a load from the persistent
compilation cache, inside set-up.  Layer: chunk program."""
from fedbench import program_spans


def read(r):
    s = program_spans.program_span(r, "repro.chunk.compile")
    return None if s is None else s.seconds
