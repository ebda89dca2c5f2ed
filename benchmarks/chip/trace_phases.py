"""Profiler trace -> the program's own spans and scopes.

`trace_reduce` keeps the harness's `bench.*` host spans and names each
device op by its HLO name.  This reader keeps two more things:

- the host spans of the program's tick path (`tick.*`, timed by
  `repro.runtime.core.Phases`), so an idle gap inside `bench.step` takes
  the innermost span that holds it: the program phase the host was in;
- each device op's name-scope path (the `tf_op` stat of its event's
  metadata, e.g. `jit(tick)/layers/while/body/closed_call/kv_slice/
  squeeze`), so device time can be put down to the `jax.named_scope`s of
  the tick program.  `jax.profiler.ProfileData` gives an event's own stats
  but not its metadata's, so these are read from the raw XSpace protobuf.

`reduce` returns `trace_reduce.reduce`'s numbers for the same events, with
`scopes_s`, `unscoped_ops` and `program_share` added.  A device event is
`[name, start_ns, dur_ns, path]`; a three-item event has no path.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

PROGRAM_SPAN_PREFIX = "tick."
STEP_SPAN = "bench.step"
OP_PATH_STAT = "tf_op"
# the tick program's named scopes (models/serve.py, distributed/pipeline.py,
# runtime/engine.py)
SCOPES = frozenset(("embed", "layers", "qkv", "kv_slice", "kv_write",
                    "attention", "kv_update", "mlp", "head", "sample"))
UNSCOPED = "unscoped"


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Dict[int, list]:
    """A protobuf message's fields by number: varints as ints, the rest
    (length-delimited, fixed 32/64-bit) as `memoryview` slices."""
    out: Dict[int, list] = defaultdict(list)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        out[key >> 3].append(value)
    return out


def _str(msg: Dict[int, list], n: int) -> str:
    return bytes(msg[n][0]).decode() if msg[n] else ""


def _int(msg: Dict[int, list], n: int) -> int:
    return msg[n][0] if msg[n] else 0


def op_paths(path: str) -> Dict[str, str]:
    """Event name (HLO text) -> `OP_PATH_STAT` of the event metadata on the
    TPU planes of an `.xplane.pb`, with its `:type` suffix dropped.

    Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5 (maps: key 1, value
    2); XEventMetadata name 2, stats 5; XStatMetadata id 1, name 2; XStat
    metadata_id 1, str_value 5, ref_value 7 (a stat metadata id whose name
    is the string)."""
    with open(path, "rb") as f:
        space = _fields(memoryview(f.read()))
    out: Dict[str, str] = {}
    for plane in map(_fields, space[1]):
        if not _str(plane, 2).startswith(tr.DEVICE_PLANE_PREFIX):
            continue
        names = {}
        for entry in plane[5]:
            meta = _fields(_fields(entry)[2][0])
            names[_int(meta, 1)] = _str(meta, 2)
        for entry in plane[4]:
            meta = _fields(_fields(entry)[2][0])
            for stat in map(_fields, meta[5]):
                if names.get(_int(stat, 1)) != OP_PATH_STAT:
                    continue
                value = (_str(stat, 5) if stat[5]
                         else names.get(_int(stat, 7), ""))
                out.setdefault(_str(meta, 2),
                               value.rpartition(":")[0] or value)
    return out


def read_xplane(path: str) -> Dict:
    """{"devices": {plane: [event]}, "host": [event]}: the ops on each TPU
    plane's "XLA Ops" line with their scope paths, and the `bench.*` and
    `tick.*` host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    paths = op_paths(path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([tr.op_name(ev.name), ev.start_ns,
                                ev.duration_ns, paths.get(ev.name, "")])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((tr.HOST_SPAN_PREFIX,
                                           PROGRAM_SPAN_PREFIX)):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "host": host}


def scope_of(path: str) -> str:
    """The innermost of `SCOPES` in a name-scope path, or `UNSCOPED`."""
    known = [part for part in path.split("/") if part in SCOPES]
    return known[-1] if known else UNSCOPED


def _covered(outer: Sequence[Tuple[float, float]],
             inner: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of `inner` that lies inside the union of
    `outer`."""
    total = 0.0
    for a, b in tr._union(outer):
        clipped = [(max(s, a), min(e, b)) for s, e in inner if e > a and s < b]
        total += sum(e - s for s, e in tr._union(clipped))
    return total


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """`trace_reduce.reduce` of the events (idle gaps labelled by the
    innermost span, the program's where one holds the gap), plus:

    scopes_s: device self seconds per program scope, summed over planes,
    each op under the innermost of `SCOPES` in its path or `UNSCOPED`; they
    partition the device time as `ops_s` does.  unscoped_ops: the `top`
    longest ops with no scope, by self time, each named with its path.
    program_share: the share of the window's `bench.step` time that the
    program's `tick.*` spans cover."""
    base = tr.reduce({"devices": {p: [e[:3] for e in evs]
                                  for p, evs in events["devices"].items()},
                      "host": events["host"]}, top)
    if base is None:
        return None
    w0, w1 = tr.window(events)
    op_ns: Dict[Tuple[str, str], float] = defaultdict(float)
    for evs in events["devices"].values():
        clipped = []
        for e in evs:
            a, b = max(e[1], w0), min(e[1] + e[2], w1)
            if b > a:
                clipped.append(((e[0], e[3] if len(e) > 3 else ""), a, b))
        tr._self_times(clipped, op_ns)
    scope_ns: Dict[str, float] = defaultdict(float)
    for (_, path), ns in op_ns.items():
        scope_ns[scope_of(path)] += ns
    base["scopes_s"] = {k: v * 1e-9 for k, v in
                        sorted(scope_ns.items(), key=lambda kv: -kv[1])}
    unscoped = sorted(((f"{name} {path}".rstrip(), ns)
                       for (name, path), ns in op_ns.items()
                       if scope_of(path) == UNSCOPED), key=lambda o: -o[1])
    base["unscoped_ops"] = [[n, t * 1e-9] for n, t in unscoped[:top]]

    def spans(pick):
        return [(max(s, w0), min(s + d, w1)) for n, s, d in events["host"]
                if pick(n) and s + d > w0 and s < w1]
    steps = spans(lambda n: n == STEP_SPAN)
    step_ns = sum(b - a for a, b in tr._union(steps))
    program = spans(lambda n: n.startswith(PROGRAM_SPAN_PREFIX))
    base["program_share"] = (_covered(steps, program) / step_ns
                             if step_ns else None)
    return base
