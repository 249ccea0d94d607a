"""Reduce a profiler trace to what the per-layer metrics read.

A trace is read into a :class:`Trace`: per device, the operations of its
``XLA Ops`` line as ``(name, start_ns, end_ns, "ops")`` and of its
``Async XLA Ops`` line with ``"async"``, names cut to the HLO instruction
(``fusion.12``), and the host's events with the thread they ran on.
On the ops line a loop (``while``) holds the operations it runs, and an
asynchronous ``*-start`` spans its transfer while other operations run.
:func:`reduce` measures, inside the traced window (the harness's
``WINDOW`` annotation on the host):

- busy time: the union of the ops line, ``*-start`` spans left out;
- per operation name, its self time (its span less the operations it
  holds);
- collective time: the union of the spans of all-to-all, all-gather,
  all-reduce, reduce-scatter and collective-permute operations (either
  line, in flight or waited on), and the part of it in which no other
  innermost operation runs (exposed);
- idle gaps: the stretches with no operation, each named by the event of
  the harness's host thread (the one the annotation is on) that overlaps
  it most.

Device numbers are averaged over the devices the cell uses.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.traced_window"
COLLECTIVE = re.compile(
    r"(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)")
ASYNC_START = re.compile(r"-start(\.\d+)?$")
Interval = Tuple[int, int]
Op = Tuple[str, int, int, str]


@dataclasses.dataclass
class Trace:
    # device name -> [(op name, start_ns, end_ns, "ops" | "async")]
    devices: Dict[str, List[Op]]
    # [(event name, start_ns, end_ns, thread name)]
    host: List[Tuple[str, int, int, str]]


LINES = {"XLA Ops": "ops", "Async XLA Ops": "async"}


def op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix) and plane.name[len(device_prefix):].isdigit():
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in LINES:
                    ops.extend((op_name(e.name), round(e.start_ns),
                                round(e.start_ns + e.duration_ns), LINES[line.name])
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, round(e.start_ns), round(e.start_ns + e.duration_ns),
                             line.name) for e in line.events)
    return Trace(devices=devices, host=host)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both unions (sorted, disjoint)."""
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


def window_of(trace: Trace) -> Tuple[int, int, str]:
    """(start_ns, end_ns, host thread) of the harness's window annotation."""
    spans = [(s, e, t) for name, s, e, t in trace.host if name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return max(spans, key=lambda x: x[1] - x[0])


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over devices
    op_s: Dict[str, float]  # self time, mean over devices
    collective_s: float  # mean over devices
    collective_exposed_s: float  # mean over devices
    idle_gaps: List[Tuple[str, float]]  # longest first, over all devices


def nest(ops: Sequence[Tuple[str, int, int]]):
    """Each op's self time and whether it holds others: [(name, s, e,
    self_ns, is_leaf)], an op holding those that lie wholly inside it."""
    out = []
    stack: List[int] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and not (out[stack[-1]][2] >= e):
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            out[stack[-1]] = (parent[0], parent[1], parent[2], parent[3] - (e - s), False)
        out.append((name, s, e, e - s, True))
        stack.append(len(out) - 1)
    return out


def reduce(trace: Trace, devices: Optional[Sequence[str]] = None,
           top_gaps: int = 10) -> Reduced:
    lo, hi, thread = window_of(trace)
    names = sorted(devices or trace.devices)
    if not names:
        raise ValueError("the trace holds no device")
    busy = coll = exposed = 0
    op_ns: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for dev in names:
        inside = [(n, max(s, lo), min(e, hi), line) for n, s, e, line in trace.devices[dev]
                  if e > lo and s < hi]
        sync = [(n, s, e) for n, s, e, line in inside
                if line == "ops" and not ASYNC_START.search(n)]
        nested = nest(sync)
        busy_u = union([(s, e) for _, s, e in sync])
        busy += length(busy_u)
        for n, _, _, self_ns, _ in nested:
            op_ns[n] += self_ns
        coll_u = union([(s, e) for n, s, e, _ in inside if COLLECTIVE.match(n)])
        coll += length(coll_u)
        compute_u = union([(s, e) for n, s, e, _, leaf in nested
                           if leaf and not COLLECTIVE.match(n)])
        exposed += length(subtract(coll_u, compute_u))
        gaps.extend(subtract([(lo, hi)], busy_u))
    k = len(names)
    host = [(n, s, e) for n, s, e, t in trace.host if t == thread and n != WINDOW]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]:
        # the host event overlapping the gap most; of equals, the innermost
        best, best_key = "no host event", (0, 0)
        for n, hs, he in host:
            key = (min(e, he) - max(s, hs), hs - he)
            if key[0] > 0 and key > best_key:
                best, best_key = n, key
        named.append((best, (e - s) / 1e9))
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / k / 1e9,
        op_s={n: v / k / 1e9 for n, v in op_ns.items()},
        collective_s=coll / k / 1e9,
        collective_exposed_s=exposed / k / 1e9,
        idle_gaps=named,
    )


def top_ops(r: Reduced, n: int = 10) -> List[Tuple[str, float]]:
    return sorted(r.op_s.items(), key=lambda kv: -kv[1])[:n]
