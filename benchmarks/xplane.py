"""Read a profiler trace (``.xplane.pb``) and reduce it to device metrics.

``jax.profiler.ProfileData`` gives planes, lines and events, but not the
statistics stored on an event's *metadata*, and that is where XLA puts the
``tf_op`` path that carries the program's ``jax.named_scope`` names
(``jit(multi_step)/while/body/closed_call/rollout/...``). So this module
decodes the XSpace protobuf itself, with nothing but the wire format
(``tsl/profiler/protobuf/xplane.proto``); tests check it against
``ProfileData`` on a trace recorded on the chip.

What a TPU v5e trace looks like (looked at by hand, PR 22): one plane per
chip, ``/device:TPU:<n>``, with the lines ``Steps``, ``XLA Modules``,
``XLA Ops`` (every HLO op, nested: a ``while`` spans the ops of its body)
and ``Async XLA Ops`` (copies, slices and collectives from their start to
their done, overlapping the rest); ``/host:CPU`` has one line per host
thread, ``python`` holding ``TraceAnnotation`` spans. An op's name is its
HLO text; a Mosaic kernel is a ``custom-call`` to ``tpu_custom_call``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import struct

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)


# --------------------------------------------------------------- wire format


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes):
    """Yield (field number, wire type, value) of one message. Varints come
    as unsigned ints, length-delimited fields as memoryview slices."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes, stat_names: dict[int, str]) -> tuple[str, object]:
    name, value = "", None
    for number, wire, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw, str(raw))
        elif number == 2:
            value = struct.unpack("<d", bytes(raw))[0]
        elif number == 3:
            value = raw
        elif number == 4:
            value = _signed(raw)
        elif number == 5:
            value = bytes(raw).decode("utf-8", "replace")
        elif number == 6:
            value = bytes(raw)
        elif number == 7:  # a reference into the stat-name table
            value = stat_names.get(raw, str(raw))
    return name, value


@dataclasses.dataclass
class Event:
    name: str
    start_ps: int  # from the start of the trace
    duration_ps: int
    stats: dict  # the event's own and its metadata's

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps

    @property
    def scope_path(self) -> str:
        """The op's ``tf_op`` path; named scopes are its components."""
        return str(self.stats.get("tf_op", ""))


@dataclasses.dataclass
class Line:
    name: str
    events: list[Event]


@dataclasses.dataclass
class Plane:
    name: str
    stats: dict
    lines: list[Line]

    def line(self, name: str) -> Line | None:
        return next((ln for ln in self.lines if ln.name == name), None)


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, _, raw in _fields(buf):
        if number == 1:
            key = raw
        elif number == 2:
            value = raw
    return key, value


def _plane(buf: bytes, keep_lines) -> Plane:
    name = ""
    raw_lines, raw_events_meta, raw_stats = [], [], []
    stat_names: dict[int, str] = {}
    for number, _, raw in _fields(buf):
        if number == 2:
            name = bytes(raw).decode()
        elif number == 3:
            raw_lines.append(raw)
        elif number == 4:
            raw_events_meta.append(raw)
        elif number == 5:
            key, value = _map_entry(raw)
            for n, _, r in _fields(value):
                if n == 2:
                    stat_names[key] = bytes(r).decode("utf-8", "replace")
        elif number == 6:
            raw_stats.append(raw)

    meta: dict[int, tuple[str, dict]] = {}
    for raw in raw_events_meta:
        key, value = _map_entry(raw)
        ev_name, ev_stats = "", {}
        for n, _, r in _fields(value):
            if n == 2:
                ev_name = bytes(r).decode("utf-8", "replace")
            elif n == 5:
                k, v = _stat(r, stat_names)
                ev_stats[k] = v
        meta[key] = (ev_name, ev_stats)

    lines = []
    for raw in raw_lines:
        line_name, timestamp_ns, raw_events = "", 0, []
        for n, _, r in _fields(raw):
            if n == 2:
                line_name = bytes(r).decode()
            elif n == 3:
                timestamp_ns = _signed(r)
            elif n == 4:
                raw_events.append(r)
        if keep_lines is not None and not keep_lines(name, line_name):
            continue
        events = []
        for r in raw_events:
            metadata_id = offset_ps = duration_ps = 0
            own: dict = {}
            for n, _, v in _fields(r):
                if n == 1:
                    metadata_id = v
                elif n == 2:
                    offset_ps = _signed(v)
                elif n == 3:
                    duration_ps = _signed(v)
                elif n == 4:
                    k, sv = _stat(v, stat_names)
                    own[k] = sv
            ev_name, ev_stats = meta.get(metadata_id, (str(metadata_id), {}))
            events.append(Event(
                ev_name, timestamp_ns * 1000 + offset_ps, duration_ps,
                {**ev_stats, **own} if own else ev_stats,
            ))
        lines.append(Line(line_name, events))
    stats = dict(_stat(r, stat_names) for r in raw_stats)
    return Plane(name, stats, lines)


def read_xspace(path: str, keep_lines=None) -> list[Plane]:
    """All planes of an ``.xplane.pb``. ``keep_lines(plane, line) -> bool``
    skips the event decoding of lines a caller does not need."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [
        _plane(raw, keep_lines)
        for number, _, raw in _fields(data) if number == 1
    ]


# ----------------------------------------------------------------- reduction


def union_ps(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_start, cur_end = 0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def merged(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def overlap_ps(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length covered by both of two merged, sorted interval lists."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def self_times(events: list[Event]) -> list[tuple[Event, int]]:
    """(event, self time in ps) for nested events of one line: an event's
    duration less the part its directly nested events cover."""
    ordered = sorted(events, key=lambda e: (e.start_ps, -e.duration_ps))
    out: list[list] = []
    stack: list[int] = []  # indices into out
    for ev in ordered:
        while stack and out[stack[-1]][0].end_ps <= ev.start_ps:
            stack.pop()
        if stack and ev.end_ps <= out[stack[-1]][0].end_ps:
            out[stack[-1]][1] -= ev.duration_ps
        out.append([ev, ev.duration_ps])
        stack.append(len(out) - 1)
    return [(ev, max(t, 0)) for ev, t in out]


def in_scope(event: Event, scope: str) -> bool:
    return f"/{scope}/" in f"/{event.scope_path}/".replace(":", "/")


def is_collective(event: Event) -> bool:
    return bool(COLLECTIVE.match(event.name.split(" = ", 1)[0].lstrip("%")))


def short_name(event: Event) -> str:
    """``%fusion.12`` plus the tail of its scope path: enough to find it."""
    head = event.name.split(" = ", 1)[0].lstrip("%")
    path = event.scope_path.rstrip(":")
    tail = "/".join(path.split("/")[-3:]) if path else ""
    return f"{head} [{tail}]" if tail else head


@dataclasses.dataclass
class DeviceTrace:
    """One chip's ops inside the traced window ``[lo_ps, hi_ps)``."""

    index: int
    ops: list[Event]  # the XLA Ops line
    async_ops: list[Event]
    lo_ps: int
    hi_ps: int

    @property
    def window_ps(self) -> int:
        return self.hi_ps - self.lo_ps

    @functools.cached_property
    def busy_intervals(self) -> list[tuple[int, int]]:
        return merged(clip(
            [(e.start_ps, e.end_ps) for e in self.ops], self.lo_ps, self.hi_ps
        ))

    def busy_ps(self) -> int:
        return sum(e - s for s, e in self.busy_intervals)

    def idle_gaps(self) -> list[tuple[int, int]]:
        gaps, cursor = [], self.lo_ps
        for start, end in self.busy_intervals:
            if start > cursor:
                gaps.append((cursor, start))
            cursor = max(cursor, end)
        if cursor < self.hi_ps:
            gaps.append((cursor, self.hi_ps))
        return gaps

    def _windowed(self, events: list[Event]) -> list[Event]:
        return [
            e for e in events
            if e.start_ps >= self.lo_ps and e.end_ps <= self.hi_ps
        ]

    @functools.cached_property
    def op_self_times(self) -> list[tuple[Event, int]]:
        """(op, self time) of every op inside the window."""
        return self_times(self._windowed(self.ops))

    def scope_ps(self, scope: str) -> int:
        """Device time under a named scope: self time of every op whose
        scope path has ``scope`` as a component."""
        return sum(t for ev, t in self.op_self_times if in_scope(ev, scope))

    def mosaic_calls(self, kernel: str = "") -> list[Event]:
        """The Mosaic custom calls; with ``kernel``, those of the Pallas
        kernel of that ``name=`` alone: XLA names the call's op by it
        (``%gqa_step.38``, ``%jvp_jit_fused_vtrace_pallas__.16``)."""
        return [
            ev for ev, _ in self.op_self_times
            if MOSAIC_TARGET in ev.name and kernel in ev.name.split(" = ", 1)[0]
        ]

    def collectives(self) -> tuple[int, int]:
        """(total, exposed) picoseconds of collective ops: their intervals
        on either line, and the part of those during which no other op
        runs on this chip."""
        coll, other = [], []
        for ev, t in self.op_self_times:
            if is_collective(ev):
                coll.append((ev.start_ps, ev.end_ps))
            elif t == ev.duration_ps:
                # Only leaves compute: a while spans its body and would
                # hide every collective inside it.
                other.append((ev.start_ps, ev.end_ps))
        coll += [
            (ev.start_ps, ev.end_ps)
            for ev in self._windowed(self.async_ops) if is_collective(ev)
        ]
        total = union_ps(coll)
        return total, total - overlap_ps(merged(coll), merged(other))

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        totals: dict[str, int] = {}
        for ev, t in self.op_self_times:
            key = short_name(ev)
            totals[key] = totals.get(key, 0) + t
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ps / 1e12) for name, ps in top]


@dataclasses.dataclass
class Trace:
    devices: list[DeviceTrace]
    annotations: list[Event]  # the host's TraceAnnotation spans

    @property
    def window_s(self) -> float:
        return self.devices[0].window_ps / 1e12

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(d.busy_ps() for d in self.devices) / len(self.devices) / 1e12

    def idle_by_annotation(self, n: int = 10) -> list[tuple[str, float]]:
        """Idle seconds of chip 0, by the innermost host annotation that
        covers the middle of each gap (``(none)`` where none does)."""
        totals: dict[str, int] = {}
        for start, end in self.devices[0].idle_gaps():
            mid = (start + end) // 2
            cover = [
                a for a in self.annotations
                if a.start_ps <= mid < a.end_ps
            ]
            name = (
                min(cover, key=lambda a: a.duration_ps).name
                if cover else "(none)"
            )
            totals[name] = totals.get(name, 0) + (end - start)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ps / 1e12) for name, ps in top]


def load_trace(path: str, window_annotation: str = "bench.window",
               annotation_prefix: str = "bench.") -> Trace | None:
    """Reduce an ``.xplane.pb`` to the ops of each chip inside the host's
    ``window_annotation`` span (the whole trace where there is none)."""

    def keep(plane: str, line: str) -> bool:
        if DEVICE_PLANE.match(plane):
            return line in (OPS_LINE, ASYNC_LINE)
        return plane == HOST_PLANE

    planes = read_xspace(path, keep)
    annotations = [
        e
        for p in planes if p.name == HOST_PLANE
        for ln in p.lines for e in ln.events
        if e.name.startswith(annotation_prefix)
    ]
    windows = [a for a in annotations if a.name == window_annotation]
    devices = []
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if not m:
            continue
        ops = (p.line(OPS_LINE) or Line(OPS_LINE, [])).events
        async_ops = (p.line(ASYNC_LINE) or Line(ASYNC_LINE, [])).events
        devices.append((int(m.group(1)), ops, async_ops))
    if not devices:
        return None  # not a chip's trace: nothing to read
    if windows:
        lo, hi = windows[0].start_ps, windows[0].end_ps
    else:
        every = [e for _, ops, _ in devices for e in ops]
        lo = min(e.start_ps for e in every)
        hi = max(e.end_ps for e in every)
    return Trace(
        [DeviceTrace(i, ops, a, lo, hi) for i, ops, a in sorted(devices)],
        annotations,
    )
