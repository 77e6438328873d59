"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The trace holds one plane per TPU chip; its ``XLA Ops`` line has one
event per device operation, whose metadata carries the operation's HLO
text and its category.  The host plane holds the harness's own spans
(``onchip.*``): ``onchip.window`` marks the measured window and the
pool's serving functions mark the host work between chunks.  Everything here is plain
interval arithmetic on those events, in nanoseconds on the trace's clock.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "onchip.window"
SPAN_PREFIX = "onchip."
# ops that only contain other ops (a scan's loop): their span is not work
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str
    start: float          # ns
    dur: float            # ns
    category: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def hlo_name(self) -> str:
        """The HLO instruction's name (the event's text up to ``=``)."""
        return self.name.split(" = ", 1)[0]

    @property
    def short(self) -> str:
        """The instruction's name and its category."""
        name = self.hlo_name
        return f"{name} ({self.category})" if self.category else name


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # chip -> ops
    spans: List[Op] = field(default_factory=list)            # host onchip.*
    window: Optional[Tuple[float, float]] = None              # ns

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def window_ops(self, chip: int) -> List[Op]:
        """The chip's operations clipped to the window."""
        lo, hi = self.window
        out = []
        for op in self.ops[chip]:
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s:
                out.append(Op(op.name, s, e - s, op.category))
        return out


# ---------------------------------------------------------------- xplane
# A minimal reader of the XSpace protobuf wire format: enough of the
# schema (tsl/profiler/protobuf/xplane.proto) to get each event's name,
# time and the stats of its metadata, which carry the HLO category and
# which the profiler's own Python view does not expose.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for varints and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat_values(stats, names: Dict[int, str]) -> Dict[str, object]:
    out = {}
    for buf in stats:
        f = dict(_fields(buf))
        name = names.get(f.get(1, -1), "")
        if 5 in f:
            out[name] = _str(f[5])
        elif 7 in f:                       # a reference to a stat name
            out[name] = names.get(f[7], "")
        else:
            out[name] = next((f[k] for k in (3, 4, 2) if k in f), None)
    return out


def _plane(buf):
    name, lines, ev_meta, st_meta = "", [], {}, {}
    for num, val in _fields(buf):
        if num == 2:
            name = _str(val)
        elif num == 3:
            lines.append(val)
        elif num in (4, 5):
            entry = dict(_fields(val))
            (ev_meta if num == 4 else st_meta)[entry.get(1, 0)] = entry.get(2)
    stat_names = {k: _str(dict(_fields(v)).get(2, b"")) for k, v in
                  st_meta.items() if v is not None}
    events = {}
    for k, v in ev_meta.items():
        fields = list(_fields(v)) if v is not None else []
        f = dict(fields)
        stats = _stat_values([x for n, x in fields if n == 5], stat_names)
        events[k] = (_str(f.get(2, b"")), str(stats.get("hlo_category", "")))
    return name, lines, events


def _line_events(buf, events):
    """``(line name, [(event name, start ns, duration ns, category)])``."""
    name, t0, out = "", 0, []
    raw = []
    for num, val in _fields(buf):
        if num == 2:
            name = _str(val)
        elif num == 3:
            t0 = val
        elif num == 4:
            raw.append(val)
    for ev in raw:
        f = dict(_fields(ev))
        ename, cat = events.get(f.get(1, 0), ("", ""))
        out.append((ename, t0 + f.get(2, 0) / 1e3, f.get(3, 0) / 1e3, cat))
    return name, out


def load(path: str) -> Trace:
    """Read the ``.xplane.pb`` at ``path`` (or the newest one under it)."""
    p = Path(path)
    if p.is_dir():
        found = sorted(glob.glob(str(p / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = Path(found[-1])
    tr = Trace()
    space = memoryview(p.read_bytes())
    for num, plane_buf in _fields(space):
        if num != 1:
            continue
        pname, lines, events = _plane(plane_buf)
        m = DEVICE_PLANE.match(pname)
        for line_buf in lines:
            lname, evs = _line_events(line_buf, events)
            if m and lname == OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    Op(n, s, d, c) for n, s, d, c in evs
                    if c not in CONTAINERS)
            elif not m:
                tr.spans.extend(Op(n, s, d) for n, s, d, _ in evs
                                if n.startswith(SPAN_PREFIX))
    wins = [s for s in tr.spans if s.name == WINDOW]
    if wins:
        w = max(wins, key=lambda s: s.dur)
        tr.window = (w.start, w.end)
    elif tr.ops:
        every = [op for ops in tr.ops.values() for op in ops]
        tr.window = (min(o.start for o in every), max(o.end for o in every))
    return tr


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, chip: int) -> float:
    return sum(e - s for s, e in union([(o.start, o.end)
                                        for o in tr.window_ops(chip)]))


def window_ns(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def mean_busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    return sum(busy_ns(tr, c) for c in tr.chips) / len(tr.chips) / 1e9


def op_time_ns(tr: Trace, chip: int, pick) -> Tuple[float, int]:
    """Summed window time and count of the chip's ops that ``pick``
    accepts (``pick(op) -> bool``)."""
    ops = [o for o in tr.window_ops(chip) if pick(o)]
    return sum(o.dur for o in ops), len(ops)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operation names with the most device time in the window,
    in seconds averaged over the chips."""
    tot: Dict[str, float] = {}
    for c in tr.chips:
        for o in tr.window_ops(c):
            tot[o.short] = tot.get(o.short, 0.0) + o.dur
    k = len(tr.chips)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10, chip: Optional[int] = None
              ) -> List[List]:
    """The ``n`` longest idle gaps of a chip in the window, each labelled
    by the harness span that covers most of it (``host`` where none does),
    in seconds."""
    chip = tr.chips[0] if chip is None else chip
    lo, hi = tr.window
    busy = union([(o.start, o.end) for o in tr.window_ops(chip)])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in tr.spans if s.name != WINDOW]

    def label(s, e):
        best, cover = "host", 0.0
        for sp in spans:
            c = min(e, sp.end) - max(s, sp.start)
            if c > cover:
                best, cover = sp.name, c
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(s, e), (e - s) / 1e9] for s, e in gaps[:n]]
