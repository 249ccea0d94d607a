"""Device time by the program's own layer names, from a profiler trace.

The program names its layers with device scopes (``robust.*``,
``model.*``, ``data.*``: ``repro.launch.spans``) and the phases of its
host loop with host spans (``setup.*``, ``train.*``).  The profiler's
``<host>.trace.json.gz``, written beside the ``.xplane.pb``, carries each
XLA op's scope path as ``tf_op``; host and device events share its clock.
:func:`reduce` measures, inside the harness's window span
(``trace.WINDOW``):

- busy time, as :func:`trace.reduce` takes it (the ``XLA Ops`` line,
  ``*-start`` spans left out);
- each op's self time (:func:`trace.nest`), given to the innermost
  ``robust.*``, ``model.*`` or ``data.*`` component of its path once
  transform wrappers (``jvp(..)``, ``transpose(..)``) are taken off, or
  to ``unscoped``; so the scopes add up to the busy time;
- the self time of ops recomputed under remat (a ``rematted_computation``
  component);
- each host span's time on the window's thread, clipped to the window,
  and how many of its spans overlap the window;
- the device's idle time, each stretch given to the shortest host span
  that holds it (or to ``no span``): where :func:`trace.reduce` names a
  whole gap after the event overlapping it most, which is an enclosing
  span whenever the gap crosses a phase boundary.

Device numbers are averaged over the devices read.

    python benchmarks/chip/scopes.py <trace dir> [--device-steps N]

prints the table of a traced run and the per-layer numbers of
:func:`per_layer`.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    # run as a script: this directory's trace.py must not shadow the
    # standard library's, and the benchmark imports by name
    HERE = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.path.insert(0, str(HERE.parents[1]))

from benchmarks.chip import trace as T  # noqa: E402

SCOPE = re.compile(r"(robust|model|data)\.[\w.]+")
SPAN = re.compile(r"(setup|train)\.[\w.]+")
WRAPPER = re.compile(r"[\w-]+\((.*)\)")
REMAT = "rematted_computation"
UNSCOPED = "unscoped"
NO_SPAN = "no span"
OPS_LINE = "XLA Ops"
# each per-layer number and the device scopes it sums
LAYERS = {
    "local_grad_ms_per_step": lambda s: s.startswith(("robust.local_grad", "model.")),
    "update_ms_per_step": lambda s: s == "robust.update",
    "robust_ms_per_step": lambda s: s in ("robust.attack", "robust.codec", "robust.aggregate"),
    "data_ms_per_step": lambda s: s == "data.lm_batch",
}
STAGE_SPAN = "train.stage_batches"
# (HLO name, start_ns, end_ns, scope path) of one device's ops line
Op = Tuple[str, int, int, str]


@dataclasses.dataclass
class Timeline:
    devices: Dict[str, List[Op]]
    # [(event name, start_ns, end_ns, thread name)], as trace.Trace.host
    host: List[Tuple[str, int, int, str]]


def events(path: str) -> Iterator[dict]:
    """The ``traceEvents`` of a (gzipped) Chrome trace, one at a time: a
    few seconds of a chip run hold hundreds of MB of JSON."""
    dec = json.JSONDecoder()
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        buf, i = "", -1
        while i < 0:
            more = fh.read(1 << 20)
            if not more:
                return
            buf += more
            i = buf.find('"traceEvents"')
        i = buf.index("[", i) + 1
        while True:
            while i < len(buf) and buf[i] in ", \n\r\t":
                i += 1
            if i < len(buf) and buf[i] == "]":
                return
            try:
                obj, end = dec.raw_decode(buf, i)
            except json.JSONDecodeError:
                more = fh.read(1 << 20)
                if not more:
                    return
                buf, i = buf[i:] + more, 0
                continue
            yield obj
            i = end
            if i > (1 << 20):
                buf, i = buf[i:], 0


def load(path: str, device_prefix: str = "/device:TPU:") -> Timeline:
    processes: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    spans = []  # the metadata naming processes and threads may come after
    for e in events(path):
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                processes[e["pid"]] = e["args"]["name"]
            elif e.get("name") == "thread_name":
                threads[(e["pid"], e["tid"])] = e["args"]["name"]
        elif e.get("ph") == "X":
            spans.append((e["pid"], e.get("tid"), e["ts"], e["dur"], e["name"],
                          (e.get("args") or {}).get("tf_op", "")))
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int, str]] = []
    for pid, tid, ts, dur, name, tf_op in spans:
        proc, thread = processes.get(pid, ""), threads.get((pid, tid), "")
        s, t = round(ts * 1000), round((ts + dur) * 1000)
        if proc.startswith(device_prefix) and proc[len(device_prefix):].isdigit():
            if thread == OPS_LINE:
                devices.setdefault(proc, []).append((name, s, t, tf_op))
        elif proc.startswith("/host:"):
            host.append((name, s, t, thread))
    return Timeline(devices=devices, host=host)


def scope_of(path: str) -> str:
    """The innermost scope of an op's path (a trace's ``tf_op``,
    ``scope/../op:type``), or ``unscoped``."""
    found = UNSCOPED
    for part in path.split("/"):
        part = part.partition(":")[0]
        while (m := WRAPPER.fullmatch(part)):
            part = m.group(1)
        if SCOPE.fullmatch(part):
            found = part
    return found


@dataclasses.dataclass
class Scoped:
    window_s: float
    busy_s: float  # mean over devices
    scope_s: Dict[str, float]  # innermost scope (or unscoped) -> self time, mean over devices
    remat_s: float  # self time of recomputed ops, mean over devices
    host_s: Dict[str, float]  # host span -> its time inside the window
    host_n: Dict[str, int]  # host span -> spans overlapping the window
    # innermost host span (or NO_SPAN) -> device idle time under it, mean over devices
    idle_s: Dict[str, float]


def _idle_by_span(idle: Sequence[Tuple[int, int]], spans: Sequence[Tuple[str, int, int]],
                  out: Dict[str, int]) -> None:
    """Adds each idle stretch to the shortest host span holding it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    for g0, g1 in idle:
        edges = [g0] + [t for t in cuts if g0 < t < g1] + [g1]
        for a, b in zip(edges, edges[1:]):
            holding = [(e - s, n) for n, s, e in spans if s <= a and b <= e]
            out[min(holding)[1] if holding else NO_SPAN] += b - a


def reduce(tl: Timeline, devices: Optional[Sequence[str]] = None) -> Scoped:
    lo, hi, thread = T.window_of(tl)
    names = sorted(devices or tl.devices)
    if not names:
        raise ValueError("the trace holds no device")
    spans = [(n, s, e) for n, s, e, t in tl.host
             if t == thread and SPAN.fullmatch(n) and e > lo and s < hi]
    busy = remat = 0
    scope_ns: Dict[str, int] = defaultdict(int)
    idle_ns: Dict[str, int] = defaultdict(int)
    for dev in names:
        sync = [(path, max(s, lo), min(e, hi)) for n, s, e, path in tl.devices.get(dev, [])
                if e > lo and s < hi and not T.ASYNC_START.search(n)]
        busy_u = T.union([(s, e) for _, s, e in sync])
        busy += T.length(busy_u)
        for path, _, _, self_ns, _ in T.nest(sync):
            scope_ns[scope_of(path)] += self_ns
            if REMAT in path.split("/"):
                remat += self_ns
        _idle_by_span(T.subtract([(lo, hi)], busy_u), spans, idle_ns)
    host_ns: Dict[str, int] = defaultdict(int)
    host_n: Dict[str, int] = defaultdict(int)
    for n, s, e in spans:
        host_ns[n] += min(e, hi) - max(s, lo)
        host_n[n] += 1
    k = len(names)
    return Scoped(window_s=(hi - lo) / 1e9, busy_s=busy / k / 1e9,
                  scope_s={n: v / k / 1e9 for n, v in scope_ns.items()},
                  remat_s=remat / k / 1e9,
                  host_s={n: v / 1e9 for n, v in host_ns.items()}, host_n=dict(host_n),
                  idle_s={n: v / k / 1e9 for n, v in idle_ns.items()})


def read(trace_dir: str, devices: Optional[Sequence[str]] = None) -> Scoped:
    """:func:`reduce` of the ``*.trace.json.gz`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} *.trace.json.gz files under {trace_dir}")
    return reduce(load(paths[0]), devices)


def per_layer(sc: Scoped, steps: int) -> Dict[str, float]:
    """The per-layer numbers of a run of ``steps`` traced steps: device
    milliseconds per step of each layer, and the host's staging
    milliseconds per window.  A program that names no layer gives none."""
    if set(sc.scope_s) <= {UNSCOPED}:
        return {}
    out = {name: 1e3 * sum(v for s, v in sc.scope_s.items() if member(s)) / steps
           for name, member in LAYERS.items()}
    if sc.host_n.get(STAGE_SPAN):
        out["host_stage_ms_per_window"] = 1e3 * sc.host_s[STAGE_SPAN] / sc.host_n[STAGE_SPAN]
    return out


def table(sc: Scoped) -> str:
    busy = sc.busy_s or 1.0
    lines = [f"window {sc.window_s:.6f} s, busy {sc.busy_s:.6f} s "
             f"({100 * sc.busy_s / (sc.window_s or 1.0):.3f}% of the window)",
             f"{'device scope':<24} {'self s':>10} {'of busy':>8}"]
    for name, v in sorted(sc.scope_s.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<24} {v:>10.6f} {100 * v / busy:>7.3f}%")
    total = sum(sc.scope_s.values())
    lines.append(f"{'sum':<24} {total:>10.6f} {100 * total / busy:>7.3f}%")
    lines.append(f"{'(recomputed, remat)':<24} {sc.remat_s:>10.6f} {100 * sc.remat_s / busy:>7.3f}%")
    lines.append(f"{'host span':<24} {'s':>10} {'spans':>8} {'idle s':>10}")
    for name, v in sorted(sc.host_s.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<24} {v:>10.6f} {sc.host_n[name]:>8} "
                     f"{sc.idle_s.get(name, 0.0):>10.6f}")
    if NO_SPAN in sc.idle_s:
        lines.append(f"{NO_SPAN:<24} {'':>10} {'':>8} {sc.idle_s[NO_SPAN]:>10.6f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--device-steps", type=int, default=2,
                    help="optimizer steps per train.window span")
    args = ap.parse_args(argv)
    sc = read(args.trace_dir)
    print(table(sc))
    windows = sc.host_n.get("train.window", 0)
    if windows:
        for name, v in per_layer(sc, windows * args.device_steps).items():
            print(f"{name} {v!r}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
