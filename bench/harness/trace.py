"""Reading a profiler trace into the numbers the per-layer metrics need.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Three things
are read from it:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane,
  each op with its start, end, HLO instruction name and program id;
* the scope path of each HLO instruction (``jax.named_scope`` lands in the
  instruction's ``op_name`` metadata), from the HLO protos the profiler
  stores in the ``/host:metadata`` plane;
* the harness's host spans (``jax.profiler.TraceAnnotation``) on the host
  plane, on the same clock as the device.

Ops nest (a ``while`` holds its body's ops, a ``conditional`` its branch's),
so every time below is a length of a union of intervals, never a sum of
durations.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?([^\s=]+)")


# --- protobuf wire format, just enough for XSpace and HloProto -------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    are skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield num, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _first(buf, num):
    for k, v in _fields(buf):
        if k == num:
            return v
    return None


def _hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata, over every computation of
    an ``HloProto`` (module: field 1; computations: 3; instructions: 2;
    instruction name: 1, metadata: 7; ``op_name``: 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for k, comp in _fields(module):
        if k != 3:
            continue
        for k2, ins in _fields(comp):
            if k2 != 2:
                continue
            name = op_name = None
            for k3, v in _fields(ins):
                if k3 == 1:
                    name = bytes(v).decode()
                elif k3 == 7:
                    op = _first(v, 2)
                    op_name = bytes(op).decode() if op is not None else ""
            if name is not None:
                out[name] = op_name or ""
    return out


def hlo_scopes(space: bytes) -> dict[int, dict[str, str]]:
    """Program id -> (instruction name -> scope path), from the HLO protos
    in the trace's ``/host:metadata`` plane."""
    out = {}
    for k, plane in _fields(space):                      # XSpace.planes
        if k != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name) != b"/host:metadata":
            continue
        for k2, entry in _fields(plane):                 # event_metadata map
            if k2 != 4:
                continue
            meta = _first(entry, 2)                      # XEventMetadata
            pid = _first(meta, 1)
            for k3, stat in _fields(meta):               # stats
                if k3 != 5:
                    continue
                blob = _first(stat, 6)                   # bytes_value
                if blob is not None and pid is not None:
                    out[pid] = _hlo_op_names(blob)
    return out


# --- the trace as intervals ----------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint sorted ``merged`` covers."""
    total = 0.0
    i = bisect.bisect_left(merged, (lo, lo))
    if i > 0 and merged[i - 1][1] > lo:
        i -= 1
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


@dataclass
class Op:
    start: float          # seconds, on the trace's clock
    end: float
    name: str             # HLO instruction name
    program: int | None   # program id


def leaves(ops: list[Op]) -> list[Op]:
    """The ops that hold no other op (``ops`` sorted by start, the longer
    first where two start together)."""
    parents, open_ = set(), []
    for i, o in enumerate(ops):
        while open_ and ops[open_[-1]].end <= o.start:
            open_.pop()
        if open_ and o.end <= ops[open_[-1]].end:
            parents.add(open_[-1])
        open_.append(i)
    return [o for i, o in enumerate(ops) if i not in parents]


@dataclass
class Trace:
    ops: dict[int, list[Op]]                       # device id -> ops
    spans: list[tuple[str, float, float]]          # host spans (name, start, end)
    scopes: dict[int, dict[str, str]] = field(default_factory=dict)

    def busy(self, device: int) -> list[tuple[float, float]]:
        return union((o.start, o.end) for o in self.ops.get(device, ()))

    def scope_of(self, op: Op) -> str:
        return self.scopes.get(op.program, {}).get(op.name, "")

    def in_scope(self, device: int, scope: str) -> list[tuple[float, float]]:
        """Device intervals of ops whose scope path holds ``scope``."""
        return union((o.start, o.end) for o in self.ops.get(device, ())
                     if scope in self.scope_of(o).split("/"))


def _plane_metadata(plane):
    """``(event metadata id -> (name, display name, program id), plane name)``."""
    name = ""
    stat_names, metas = {}, []
    for k, v in _fields(plane):
        if k == 2:
            name = bytes(v).decode()
        elif k == 5:                                     # stat_metadata map
            sm = _first(v, 2)
            stat_names[_first(sm, 1)] = bytes(_first(sm, 2) or b"").decode()
        elif k == 4:                                     # event_metadata map
            metas.append(_first(v, 2))
    events = {}
    for em in metas:
        mid, ename, display, prog = None, "", "", None
        for k, v in _fields(em):
            if k == 1:
                mid = v
            elif k == 2:
                ename = bytes(v).decode(errors="replace")
            elif k == 4:
                display = bytes(v).decode(errors="replace")
            elif k == 5 and stat_names.get(_first(v, 1)) == "program_id":
                prog = _first(v, 3)
        events[mid] = (ename, display, prog)
    return events, name


def _line_events(line):
    """``(line name, [(metadata id, start ps, duration ps), ...])``."""
    name, ts_ns, events = "", 0, []
    for k, v in _fields(line):
        if k == 2:
            name = bytes(v).decode()
        elif k == 3:
            ts_ns = v
        elif k == 4:
            mid = off = dur = 0
            for k2, v2 in _fields(v):
                if k2 == 1:
                    mid = v2
                elif k2 == 2:
                    off = v2
                elif k2 == 3:
                    dur = v2
            events.append((mid, off, dur))
    return name, [(m, ts_ns * 1000 + o, d) for m, o, d in events]


def load(space: bytes, span_names) -> Trace:
    """Device ops and the named host spans of one serialized ``XSpace``.
    Times are seconds from the first event read."""
    raw_ops: dict[int, list] = {}
    raw_spans = []
    scopes = {}
    for k, plane in _fields(space):
        if k != 1:
            continue
        metas, pname = _plane_metadata(plane)
        m = _DEVICE_PLANE.match(pname)
        if pname == "/host:metadata":
            scopes = hlo_scopes(space)
            continue
        if not (m or pname.startswith("/host:CPU")):
            continue
        for k2, line in _fields(plane):
            if k2 != 3:
                continue
            lname, events = _line_events(line)
            if m and lname == "XLA Ops":
                dev = raw_ops.setdefault(int(m.group(1)), [])
                for mid, s, d in events:
                    ename, display, prog = metas.get(mid, ("", "", None))
                    instr = display or _INSTR.match(ename).group(1)
                    dev.append((s, s + d, instr, prog))
            elif not m:
                for mid, s, d in events:
                    ename = metas.get(mid, ("",))[0]
                    if ename in span_names:
                        raw_spans.append((ename, s, s + d))
    starts = [o[0] for ops in raw_ops.values() for o in ops]
    starts += [s for _, s, _ in raw_spans]
    t0 = min(starts) if starts else 0
    ops = {d: sorted((Op((s - t0) * 1e-12, (e - t0) * 1e-12, n, p)
                      for s, e, n, p in v), key=lambda o: (o.start, -o.end))
           for d, v in raw_ops.items()}
    spans = sorted(((n, (s - t0) * 1e-12, (e - t0) * 1e-12)
                    for n, s, e in raw_spans), key=lambda x: x[1])
    return Trace(ops=ops, spans=spans, scopes=scopes)


def read_file(path) -> bytes:
    """The serialized ``XSpace`` in ``path`` (gzip-compressed if it ends in
    ``.gz``)."""
    import gzip

    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()
