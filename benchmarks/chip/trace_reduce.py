"""Profiler trace -> device busy time, per-op device time, and idle gaps
labelled by the harness's host spans.

Two steps, so that the second can be checked on a small recorded trace:
`read_xplane` turns the profiler's `.xplane.pb` into plain events, and
`reduce` turns events into numbers.  Events are `[name, start_ns, dur_ns]`
on one clock.  The traced window is the harness's `bench.window` span.

On a TPU each op event on a device plane's "XLA Ops" line is named by its
HLO text (`%copy.55 = bf16[1,24,2978,...]{...} copy(...)`); an op is named
here by its HLO name and result shape (`copy.55 bf16[1,24,2978,...]`).  A
loop op (`while`) spans the ops of its body, which the same line lists too,
so per-op times are self times: an op's time minus that of the ops inside
it.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_HLO_NAME = re.compile(r"^%?([\w.\-]+) = (\w+\[[\d,]*\])?")

Event = Tuple[str, float, float]


def op_name(text: str) -> str:
    """`copy.55 bf16[1,24]` from `%copy.55 = bf16[1,24]{1,0} copy(...)`;
    other names unchanged."""
    m = _HLO_NAME.match(text)
    if not m:
        return text
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def read_xplane(path: str) -> Dict:
    """{"devices": {plane: [event]}, "host": [event]}: the ops on each TPU
    plane's "XLA Ops" line, and the harness's `bench.*` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns,
                                ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"devices": devices, "host": host}


def load_events(path: str) -> Dict:
    """Events saved by `save_events` (or `read_xplane` of a `.xplane.pb`)."""
    if path.endswith(".xplane.pb"):
        return read_xplane(path)
    with open(path) as f:
        return json.load(f)


def save_events(events: Dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(events, f)


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(events: Dict) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def _self_times(clipped: Sequence[Tuple[str, float, float]],
                op_ns: Dict[str, float]) -> None:
    """Add each op's self time to `op_ns`: its interval minus those of the
    ops nested inside it."""
    stack: List[List] = []      # [name, end, duration, time of ops inside]

    def close() -> None:
        name, _, duration, inner = stack.pop()
        op_ns[name] += duration - inner
        if stack:
            stack[-1][3] += duration
    for name, a, b in sorted(clipped, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close()
        stack.append([name, b, b - a, 0.0])
    while stack:
        close()


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """Numbers of the traced window, or None when no device op ran in it.

    busy_s: the union of the intervals in which an op ran, clipped to the
    window, averaged over the device planes.  ops_s: self seconds per op
    name, summed over planes; device_ops: the `top` longest.  idle_gaps:
    the `top` longest gaps between busy intervals on the first plane, each
    labelled by the harness span (innermost) that holds its midpoint, or
    "none"."""
    w0, w1 = window(events)
    per_plane_busy = []
    op_ns: Dict[str, float] = defaultdict(float)
    first_busy = None
    for plane in sorted(events["devices"]):
        clipped = []
        for name, s, d in events["devices"][plane]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name, a, b))
        _self_times(clipped, op_ns)
        busy = _union([(a, b) for _, a, b in clipped])
        per_plane_busy.append(sum(b - a for a, b in busy))
        if first_busy is None:
            first_busy = busy
    if not per_plane_busy or not any(per_plane_busy):
        return None
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n != WINDOW_SPAN]
    gaps = []
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_label((a + b) / 2, spans), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(((n, t * 1e-9) for n, t in op_ns.items()),
                 key=lambda o: -o[1])
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(per_plane_busy) / len(per_plane_busy) * 1e-9,
            "ops_s": dict(ops),
            "device_ops": [[n, t] for n, t in ops[:top]],
            "idle_gaps": [[n, t] for n, t in gaps[:top]]}


def _label(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    holding = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(holding)[1] if holding else "none"
