"""From the profiler's trace to numbers: which planes are devices, the union
of the intervals in which an operation ran there, the idle gaps and what the
host was doing in them. Reads the `.xplane.pb` through
`jax.profiler.ProfileData` (nothing but JAX). The arithmetic works on plain
lists of (start, duration) so that it is tested without a trace
(tests/benchmark/test_harness.py)."""

from __future__ import annotations

import glob
import os

#: device-plane lines that hold one event per executed operation. The other
#: lines ("Steps", "XLA Modules", ...) span whole programs, idle gaps inside
#: them included, so they would read as 100 % busy.
OP_LINES = ("XLA Ops",)
#: one event per execution of a compiled program
MODULE_LINE = "XLA Modules"


def busy_union(events: list[tuple[float, float]]) -> float:
    """Length of the union of intervals (start, duration)."""
    total, end = 0.0, float("-inf")
    for t0, dur in sorted(events):
        t1 = t0 + dur
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def idle_gaps(events: list[tuple[float, float]], t0: float, t1: float
              ) -> list[tuple[float, float]]:
    """(start, length) of the stretches of [t0, t1] no interval covers."""
    gaps, end = [], t0
    for s, dur in sorted(events):
        if s > end:
            gaps.append((end, min(s, t1) - end))
        end = max(end, s + dur)
        if end >= t1:
            break
    if end < t1:
        gaps.append((end, t1 - end))
    return [g for g in gaps if g[1] > 0]


def name_gaps(gaps: list[tuple[float, float]],
              host: list[tuple[str, float, float]], top: int = 10
              ) -> list[list]:
    """Idle seconds by the innermost host annotation (name, start, duration)
    open at each gap's middle; "(none)" where there is none."""
    by_name: dict[str, float] = {}
    for g0, length in gaps:
        mid = g0 + length / 2
        open_ = [(dur, name) for name, s, dur in host if s <= mid <= s + dur]
        name = min(open_)[1] if open_ else "(none)"
        by_name[name] = by_name.get(name, 0.0) + length
    return [[n, s] for n, s in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def self_times(events: list[tuple[str, float, float]]
               ) -> list[tuple[str, float]]:
    """(name, seconds not covered by a nested event) of events (name, start,
    duration) of ONE line, where a loop's event spans the events of its
    body: without this a `while` reads as all of the time."""
    out, stack = [], []   # stack: [name, end, self seconds]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out.append((name, max(own, 0.0)))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def short_name(op: str) -> str:
    """`%fusion.359 = (f32[10,32]{...}, ...) fusion(...), kind=kOutput` ->
    `fusion.359 f32[10,32] kOutput`: the op, its first result's type and the
    fusion kind, without layouts and operands."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:96]
    shape = rest.lstrip("(").split("{")[0].split(" ")[0].rstrip(",")
    kind = rest.rsplit("kind=", 1)[1].split(",")[0] if "kind=" in rest else ""
    return " ".join(x for x in (head.lstrip("%"), shape, kind) if x)[:96]


def modules(events: list[tuple[str, float]]) -> list[list]:
    """[[program name, executions, device seconds]], most seconds first, of
    (name, duration) events of the modules line; the run's hash in
    `jit_round_fn(123...)` is dropped."""
    by_name: dict[str, list] = {}
    for name, dur in events:
        row = by_name.setdefault(name.split("(")[0], [0, 0.0])
        row[0] += 1
        row[1] += dur
    return [[n, c, s] for n, (c, s) in
            sorted(by_name.items(), key=lambda kv: -kv[1][1])]


def ops_by_name(ops: list[tuple[str, float]]) -> list[list]:
    """[[name, seconds, executions]] of (name, seconds) events, most seconds
    first: every op, for a reader that looks for its kernel by name."""
    by_name: dict[str, list] = {}
    for name, dur in ops:
        row = by_name.setdefault(name, [0.0, 0])
        row[0] += dur
        row[1] += 1
    return [[n, s, c] for n, (s, c) in
            sorted(by_name.items(), key=lambda kv: -kv[1][0])]


def newest(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


def read(trace_dir: str, chips: int) -> dict | None:
    """-> {"busy_s" (mean over chips), "window_s", "modules" (of the first
    chip), "ops" (every op of the first chip: [short name, self seconds,
    executions]), "device_ops" (the ten with most seconds, for the
    breakdown), "idle_gaps"} of the newest trace under `trace_dir`, or None
    where there is no trace or no device plane in it."""
    from jax.profiler import ProfileData

    path = newest(trace_dir)
    if path is None:
        return None
    data = ProfileData.from_file(path)
    devices, host, programs = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            if not programs:
                programs = [(e.name, e.duration_ns * 1e-9)
                            for line in plane.lines
                            if line.name == MODULE_LINE for e in line.events]
            ev = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                  for line in plane.lines if line.name in OP_LINES
                  for e in line.events]
            if ev:
                devices.append(ev)
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                     for line in plane.lines for e in line.events
                     if e.name.startswith("host:")]
    devices = devices[:chips]
    if not devices:
        return None
    marks = [(s, s + d) for _, s, d in host]
    every = [(s, d) for ev in devices for _, s, d in ev]
    t0 = min([s for s, _ in every] + [m[0] for m in marks])
    t1 = max([s + d for s, d in every] + [m[1] for m in marks])
    busy = [busy_union([(s, d) for _, s, d in ev]) for ev in devices]
    first = [(s, d) for _, s, d in devices[0]]
    ops = ops_by_name([(short_name(n), d) for n, d in self_times(devices[0])])
    return {
        "busy_s": sum(busy) / len(busy), "window_s": t1 - t0,
        "modules": modules(programs),
        "ops": ops, "device_ops": [[n, s] for n, s, _ in ops[:10]],
        "idle_gaps": name_gaps(idle_gaps(first, t0, t1), host),
    }
