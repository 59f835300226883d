"""Reduction of a profiler trace (`.xplane.pb`) to the device numbers the
benchmark reports: busy and idle time, device time per operation,
collective time with and without compute beside it, and the longest idle
gaps labelled by what the host was doing.

`load(path)` reads the file with JAX's own `ProfileData` into plain
`Trace` intervals; everything after that is interval arithmetic on
nanoseconds, tested on a recorded trace and on made-up intervals.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

# Lines of a device plane that hold one event per executed operation.
OPS_LINE = "XLA Ops"
# HLO names of the operations that move data between chips.
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum", re.IGNORECASE)
# Host spans the benchmark writes (jax.profiler.TraceAnnotation) around its
# calls into the program; an idle gap is named after the one it falls in.
HOST_SPAN = re.compile(r"^bench\.")
WINDOW_SPAN = "bench.window"
# Control flow whose event spans the ops of its body: counted as busy, but
# not as an op of its own (its body's ops are).
CONTAINER = re.compile(r"\)?\s(while|conditional|call)\(")
OUTSIDE = "host outside every bench span"


@dataclasses.dataclass
class Op:
    name: str           # the HLO instruction's name, e.g. "fusion.12"
    detail: str         # the event's full text: the instruction with its
                        # operand shapes and custom-call target
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict       # device plane name -> [Op] (sorted by start)
    host: list          # [(name, start_ns, end_ns)] benchmark host spans


def _op_name(text: str) -> str:
    """A TPU trace names each op by its HLO text, "%fusion.12 = bf16[...]
    fusion(...), kind=..."; the instruction name is the part before " = "."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(path) -> Trace:
    """Every device plane's operations and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(_op_name(ev.name), ev.name,
                                  int(ev.start_ns), int(ev.duration_ns)))
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    host.sort(key=lambda h: h[1])
    return Trace(devices, host)


def find_xplane(directory) -> Path | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of union ``a`` not covered by union ``b``."""
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


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(trace: Trace, window_ns: tuple[int, int] | None = None) -> dict:
    """Device numbers over ``window_ns`` (default: first to last op).

    busy_s        mean over devices of the union of op intervals
    window_s      length of the window
    op_s          {op name: device seconds summed over devices}; loops
                  and other control flow count through their body's ops
    op_detail     {op name: its longer names, for matching kernels}
    collective_s  mean over devices of time in collective ops
    exposed_s     mean over devices of collective time with no other op
                  running on that device
    idle_gaps     [(host span name, seconds)], the ten longest
    """
    if not trace.devices:
        return {}
    if window_ns is None:
        window_ns = (min(o.start for ops in trace.devices.values()
                         for o in ops),
                     max(o.end for ops in trace.devices.values()
                         for o in ops))
    lo, hi = window_ns
    n = len(trace.devices)
    busy = coll = exposed = 0
    op_s: dict[str, float] = {}
    detail: dict[str, str] = {}
    gaps = []
    for ops in trace.devices.values():
        ops = [o for o in ops if o.end > lo and o.start < hi]
        u = union(clip([(o.start, o.end) for o in ops], lo, hi))
        busy += length(u)
        c = union(clip([(o.start, o.end) for o in ops
                        if COLLECTIVE.search(o.name)], lo, hi))
        other = union(clip([(o.start, o.end) for o in ops
                            if not COLLECTIVE.search(o.name)], lo, hi))
        coll += length(c)
        exposed += length(subtract(c, other))
        for o in ops:
            if CONTAINER.search(o.detail.split(" = ", 1)[-1][:400]):
                continue
            s, e = max(o.start, lo), min(o.end, hi)
            op_s[o.name] = op_s.get(o.name, 0.0) + (e - s) * 1e-9
            if o.detail and o.name not in detail:
                detail[o.name] = o.detail
        gaps.extend(subtract([(lo, hi)], u))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "op_s": op_s,
        "op_detail": detail,
        "collective_s": coll / n * 1e-9,
        "exposed_s": exposed / n * 1e-9,
        "idle_gaps": [(host_activity(trace.host, s, e), (e - s) * 1e-9)
                      for s, e in gaps[:10]],
        "devices": n,
    }


def host_activity(spans, start: int, end: int) -> str:
    """Name of the benchmark host span (other than the window's own) that
    covers at least half of [start, end), else `OUTSIDE`. The device's
    clock in a TPU trace runs a millisecond or two ahead of the host's, so
    a gap shorter than that is named only roughly."""
    best, name = (end - start) / 2, OUTSIDE
    for sname, s, e in spans:
        if s >= end:
            break
        ov = min(e, end) - max(s, start)
        if sname != WINDOW_SPAN and ov >= best:
            best, name = ov, sname
    return name


def kernel_seconds(red: dict, *patterns: str) -> float | None:
    """Device seconds of the ops whose full text matches every pattern
    (summed over devices); None if none ran."""
    rxs = [re.compile(p) for p in patterns]
    hits = [s for name, s in red.get("op_s", {}).items()
            if all(rx.search(red["op_detail"].get(name, name))
                   for rx in rxs)]
    return sum(hits) if hits else None


def breakdown(red: dict) -> dict:
    """The contract's ``breakdown``: the ten device operations that took
    most time and the ten longest idle gaps by host activity."""
    top = sorted(red.get("op_s", {}).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in red.get("idle_gaps", [])]}
