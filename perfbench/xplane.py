"""From the profiler's ``.xplane.pb`` to numbers, with nothing but jax.

What a TPU trace looks like (looked at by hand on the v5e, PR 22): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per HLO instruction that ran on the
core, named by the instruction's whole text, ``%fusion.6 = bf16[...] fusion(
...)``) and ``Async XLA Ops`` (the time between an asynchronous
instruction's start and its done: copies in flight, which occupy no core;
seen on the first device only). Under the partitioner a v5e's large
collectives are not ``all-gather-start`` instructions but custom fusions named
``%async-collective-start.<n>`` and ``%async-collective-done.<n>``, with the
compute the compiler overlapped with them in between; small ones are plain
``all-gather`` and ``all-reduce`` instructions, which can overlap compute too.
The overlapped compute is itself part of the ring: a matmul fusion that also
moves a step of the gather (``calls=%async_collective_fusion.<n>``, its result
a tuple with the shard, the gathered buffers and some fifteen semaphores), so
a wait on a neighbour inside it is counted as compute, and the time no
compute runs beside a collective says little of what the collective costs.
The plane ``/host:CPU`` has a line per thread, named after the thread; the
harness's ``perfbench.*`` annotations are on the line of the thread that made
them. Host and device events share one clock.

Busy time is the union of the ``XLA Ops`` intervals. The reduced window runs
from the start of a device's first program to the end of its last, so the
profiler's own start and stop are not in it and the gaps between programs are.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE, SPAN_PREFIX = "/host:CPU", "perfbench."

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all",
               "collective-broadcast", "ragged-all-to-all")
ASYNC_START, ASYNC_DONE = "async-collective-start", "async-collective-done"
CARRIES_A_COLLECTIVE = "calls=%async_collective_fusion"
_INSTRUCTION = re.compile(r"^%(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"\b(?:pred|[subf]\d+|bf16|c64|c128|token)\[[\d,]*\]")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: list  # XLA Ops, sorted by start
    in_flight: list  # Async XLA Ops
    programs: list  # XLA Modules


@dataclasses.dataclass
class Trace:
    devices: list  # DeviceTrace, by ordinal
    host_spans: list  # perfbench.* annotations


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> list:
    return sorted((Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events), key=lambda e: (e.start, -e.end))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                ordinal=int(m.group(1)),
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                in_flight=_events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [],
                programs=_events(lines[MODULES_LINE]) if MODULES_LINE in lines else []))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [e for e in _events(line) if e.name.startswith(SPAN_PREFIX)]
    return Trace(sorted(devices, key=lambda d: d.ordinal), sorted(spans, key=lambda e: e.start))


# -----------------------------------------------------------------------------
# Instruction text
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)  # a trace repeats a few thousand texts every step, and every reader asks
def instruction(name: str) -> tuple[str, str, str]:
    """(instruction name, opcode, result shape) of an ``XLA Ops`` event name.
    ``%fusion.6 = (bf16[8,4]{...}, f32[4]{...}) fusion(...), kind=...`` ->
    ``("fusion.6", "fusion", "(bf16[8,4],f32[4])")``. A name that is not an
    instruction's text comes back as ``(name, "", "")``."""
    m = _INSTRUCTION.match(name)
    if not m:
        return name, "", ""
    rest = m.group("rest")
    depth, i = 0, 0
    if rest.startswith("("):  # a tuple shape: skip to its closing parenthesis
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape_text, tail = rest[: i + 1], rest[i + 1:]
    else:
        shape_text, _, tail = rest.partition(" ")
        tail = " " + tail
    op = re.match(r"\s*([a-z][\w\-]*)\(", tail)
    shapes = _SHAPE.findall(shape_text)
    shape = shapes[0] if len(shapes) == 1 and not shape_text.startswith("(") else "(" + ",".join(shapes) + ")"
    return m.group("name"), op.group(1) if op else "", shape


def is_mosaic_kernel(name: str) -> bool:
    return instruction(name)[1] == "custom-call" and 'custom_call_target="tpu_custom_call"' in name


def carries_a_collective(name: str) -> bool:
    """A compute fusion that also moves a step of an asynchronous collective."""
    return CARRIES_A_COLLECTIVE in name and not is_collective(name)


def is_collective(name: str) -> bool:
    instr, op, _ = instruction(name)
    return (instr.startswith((ASYNC_START, ASYNC_DONE))
            or any(op == c or op == c + "-start" or op == c + "-done" for c in COLLECTIVES))


# -----------------------------------------------------------------------------
# Interval arithmetic
# -----------------------------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[tuple[float, float]]:
    """The points of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_seconds(events: list) -> list[tuple[Event, float]]:
    """Each event with its own time: its length less what the events nested
    inside it cover (a ``while`` holds its body's instructions). ``events`` is
    sorted by start, longer first on a tie."""
    out, stack = [], []  # stack of [event, seconds covered by children]

    def close(upto: float):
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            out.append((ev, max(ev.seconds - covered, 0.0)))
            if stack:
                stack[-1][1] += ev.seconds

    for ev in events:
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


# -----------------------------------------------------------------------------
# Reduction
# -----------------------------------------------------------------------------


def window_of(device: DeviceTrace) -> tuple[float, float]:
    """From the start of the device's first program to the end of its last."""
    marks = device.programs or device.ops
    if not marks:
        raise ValueError(f"no operation ran on device {device.ordinal} in the traced window")
    return min(e.start for e in marks), max(e.end for e in marks)


def busy_intervals(device: DeviceTrace) -> list[tuple[float, float]]:
    lo, hi = window_of(device)
    return clip(union((e.start, e.end) for e in device.ops), lo, hi)


def busy_and_window(trace: Trace) -> tuple[float, float]:
    """(busy seconds, window seconds), each averaged over the devices."""
    n = len(trace.devices)
    busy = sum(total(busy_intervals(d)) for d in trace.devices) / n
    window = sum(window_of(d)[1] - window_of(d)[0] for d in trace.devices) / n
    return busy, window


def class_seconds(device: DeviceTrace) -> dict:
    """Self time of the device's instructions by class: ``kernel`` (Mosaic
    custom calls), ``collective`` and ``xla`` (everything else)."""
    out = {"kernel": 0.0, "collective": 0.0, "xla": 0.0}
    for ev, own in self_seconds(device.ops):
        cls = "kernel" if is_mosaic_kernel(ev.name) else "collective" if is_collective(ev.name) else "xla"
        out[cls] += own
    return out


def fused_with_collective_seconds(device: DeviceTrace) -> float:
    """Self time of the compute fusions that carry a step of a collective: part
    of ``class_seconds``'s ``xla``."""
    return sum(own for ev, own in self_seconds(device.ops) if carries_a_collective(ev.name))


def collective_intervals(device: DeviceTrace) -> list[tuple[float, float]]:
    """When a collective was running or in flight: every collective
    instruction on ``XLA Ops`` and ``Async XLA Ops``, and the time from each
    ``async-collective-start.<n>`` to the ``async-collective-done.<n>`` after it."""
    spans = [(e.start, e.end) for e in device.ops + device.in_flight if is_collective(e.name)]
    started: dict[str, float] = {}
    for e in device.ops:
        instr = instruction(e.name)[0]
        if instr.startswith(ASYNC_START):
            started[instr[len(ASYNC_START):]] = e.start
        elif instr.startswith(ASYNC_DONE) and instr[len(ASYNC_DONE):] in started:
            spans.append((started.pop(instr[len(ASYNC_DONE):]), e.end))
    return union(spans)


def compute_intervals(device: DeviceTrace) -> list[tuple[float, float]]:
    """When the core ran anything that is not a collective."""
    return union((e.start, e.end) for e in device.ops
                 if not is_collective(e.name) and instruction(e.name)[1] not in ("while", "conditional", "call"))


def collective_and_exposed(device: DeviceTrace) -> tuple[float, float]:
    """(seconds a collective was running or in flight, the part of them during
    which no compute ran on this device)."""
    lo, hi = window_of(device)
    coll = clip(collective_intervals(device), lo, hi)
    return total(coll), total(subtract(coll, compute_intervals(device)))


def busiest(trace: Trace) -> DeviceTrace:
    return max(trace.devices, key=lambda d: total(busy_intervals(d)))


def idle_gaps_by_host_span(trace: Trace) -> list[tuple[str, float]]:
    """Idle seconds of the busiest device, summed by what the host was doing at
    the middle of each gap (the innermost ``perfbench.*`` span) and by whether
    the gap lay inside a program or between two. Longest first."""
    device = busiest(trace)
    lo, hi = window_of(device)
    programs = union((p.start, p.end) for p in device.programs)
    sums: dict[str, float] = {}
    for s, e in subtract([(lo, hi)], busy_intervals(device)):
        mid = (s + e) / 2
        covering = [sp for sp in trace.host_spans if sp.start <= mid < sp.end]
        host = min(covering, key=lambda sp: sp.seconds).name if covering else "no perfbench span"
        where = "inside a program" if any(ps <= mid < pe for ps, pe in programs) else "between programs"
        key = f"{host}, {where}"
        sums[key] = sums.get(key, 0.0) + (e - s)
    return sorted(sums.items(), key=lambda kv: -kv[1])
