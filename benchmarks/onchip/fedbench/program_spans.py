"""The program's own host spans (``repro.telemetry``) for the per-layer
readers, taken from the in-memory record of the newest ``FederatedJob.run``
in this process: the harness deletes the profiler trace before the readers
run, and the trace keeps only the harness's spans.

A chunk's spans are selected by round, not by clock.  The window holds
rounds ``[warm, warm + rounds)``, with ``warm`` the first chunk boundary at
or past the rounds ``correct`` checks.  A program without the telemetry
module (an older checkout) has no spans, and every reader returns ``None``.
"""
from __future__ import annotations

from typing import Any, List


def chunk_rounds(cell) -> int:
    tr = cell.traffic
    return 1 if tr["engine"] == "sharded" else int(tr["chunk_rounds"])


def recorded() -> List[Any]:
    try:
        from repro import telemetry
    except ImportError:
        return []
    return telemetry.spans()


def window_chunks(r):
    """``(chunk spans, {chunk id: [child spans]})`` of the window."""
    every = recorded()
    k = chunk_rounds(r.cell)
    warm = k * -(-int(r.cell.traffic["first_rounds"]) // k)
    chunks = [s for s in every if s.name == "repro.chunk"
              and warm <= s.attrs.get("round", -1) < warm + r.rounds]
    ids = {s.id for s in chunks}
    children = {i: [] for i in ids}
    for s in every:
        if s.parent in ids:
            children[s.parent].append(s)
    return chunks, children


def program_span(r, name: str):
    """The ``name`` span (``repro.chunk.lower`` or ``.compile``) of the
    chunk program the window runs, or ``None``."""
    k = chunk_rounds(r.cell)
    found = [s for s in recorded()
             if s.name == name and s.attrs.get("rounds") == k]
    return found[-1] if found else None
