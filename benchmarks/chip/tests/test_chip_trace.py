"""The trace reduction (``trace.py``) on a small trace with known answers,
and against a brute-force count on random ones."""
import random

import pytest

from chip_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import trace as T

W = T.WINDOW


def small_trace():
    return T.Trace(
        devices={
            "/device:TPU:0": [
                ("fusion.0", 20, 90, "ops"),  # before the window
                ("while.1", 100, 700, "ops"),  # holds fusion.2 and the done
                ("fusion.2", 150, 400, "ops"),
                ("all-gather-start.3", 380, 390, "ops"),  # in flight, not busy
                ("all-gather.3", 390, 600, "async"),
                ("all-gather-done.3", 550, 650, "ops"),  # the wait for it
                ("fusion.4", 800, 900, "ops"),
                ("all-to-all.5", 900, 950, "ops"),
                ("convolution.6", 1000, 1200, "ops"),  # ends after the window
            ],
            "/device:TPU:1": [("fusion.1", 100, 1100, "ops")],
        },
        host=[
            (W, 100, 1100, "main"),
            ("PjitFunction(window)", 690, 820, "main"),
            ("DevicePut", 700, 760, "main"),
            ("np.asarray(jax.Array)", 940, 1005, "main"),
            ("runtime thread", 0, 2000, "other"),
        ],
    )


def test_small_trace_exactly():
    r = T.reduce(small_trace())
    assert r.window_s == 1000 / 1e9
    # device 0: busy 100-700, 800-950, 1000-1100 = 850 ns; device 1: 1000 ns
    assert r.busy_s == 925 / 1e9
    # self times: the loop holds 250 + 100 ns of its 600
    assert r.op_s == {"while.1": 125 / 1e9, "fusion.2": 125 / 1e9,
                      "all-gather-done.3": 50 / 1e9, "fusion.4": 50 / 1e9,
                      "all-to-all.5": 25 / 1e9, "convolution.6": 50 / 1e9,
                      "fusion.1": 500 / 1e9}
    # device 0: collectives over 380-650 and 900-950; exposed 400-650, 900-950
    assert r.collective_s == 160 / 1e9
    assert r.collective_exposed_s == 150 / 1e9
    # device 0's gaps 700-800 and 950-1000, named by the harness thread's
    # event that overlaps each most
    assert r.idle_gaps == [("PjitFunction(window)", 100 / 1e9),
                           ("np.asarray(jax.Array)", 50 / 1e9)]
    assert T.top_ops(r, 3) == [("fusion.1", 500 / 1e9), ("while.1", 125 / 1e9),
                               ("fusion.2", 125 / 1e9)]


def test_only_listed_devices_count():
    r = T.reduce(small_trace(), devices=["/device:TPU:1"])
    assert r.busy_s == 1000 / 1e9 and r.collective_s == 0 and r.idle_gaps == []


def test_op_names_are_the_hlo_instruction():
    assert T.op_name("%fusion.12 = bf16[8]{0} fusion(%a), kind=kLoop") == "fusion.12"


def _brute(ops, lo, hi):
    """Busy, collective and exposed nanoseconds, painted one at a time."""
    ops = [(n, max(s, lo), min(e, hi), line) for n, s, e, line in ops if e > lo and s < hi]
    sync = [(n, s, e) for n, s, e, line in ops if line == "ops" and not T.ASYNC_START.search(n)]

    def holds(x, y):
        return x is not y and x[1] <= y[1] and y[2] <= x[2]

    leaves = [x for x in sync if not any(holds(x, y) for y in sync)]
    busy = coll = exposed = 0
    for t in range(lo, hi):
        is_busy = any(s <= t < e for _, s, e in sync)
        is_coll = any(s <= t < e for n, s, e, _ in ops if T.COLLECTIVE.match(n))
        computes = any(s <= t < e for n, s, e in leaves if not T.COLLECTIVE.match(n))
        busy += is_busy
        coll += is_coll
        exposed += is_coll and not computes
    return busy, coll, exposed


@pytest.mark.parametrize("seed", range(5))
def test_random_traces_match_brute_force(seed):
    rng = random.Random(seed)
    names = ["fusion.1", "all-reduce.2", "copy-start.3", "all-to-all.4", "reduce-scatter.5",
             "all-gather-start.6"]
    ops, seen = [], set()
    while len(ops) < 25:
        s = rng.randrange(0, 400)
        op = (rng.choice(names), s, s + rng.randrange(1, 60), rng.choice(["ops", "ops", "async"]))
        if op[1:3] not in seen:
            seen.add(op[1:3])
            ops.append(op)
    lo, hi = 40, 380
    trace = T.Trace(devices={"/device:TPU:0": ops}, host=[(W, lo, hi, "main")])
    r = T.reduce(trace)
    busy, coll, exposed = _brute(ops, lo, hi)
    assert round(r.busy_s * 1e9) == busy
    assert round(r.collective_s * 1e9) == coll
    assert round(r.collective_exposed_s * 1e9) == exposed
    assert sum(round(g * 1e9) for _, g in r.idle_gaps) <= (hi - lo) - busy


def test_recorded_excerpt_matches_a_timeline():
    """A few milliseconds of a chip trace (one window step on a TPU v5e),
    reduced by ``trace.py`` and by painting each op on a nanosecond
    timeline: the two agree exactly."""
    import json

    import numpy as np

    data = json.loads((ROOT / "benchmarks/chip/tests/data/trace_excerpt.json").read_text())
    trace = T.Trace(devices={d: [tuple(e) for e in ev] for d, ev in data["devices"].items()},
                    host=[tuple(e) for e in data["host"]])
    r = T.reduce(trace)
    lo, hi = data["window"]
    busy = exposed = coll = 0
    for ops in trace.devices.values():
        any_op = np.zeros(hi - lo, bool)
        compute = np.zeros(hi - lo, bool)
        collective = np.zeros(hi - lo, bool)
        clipped = [(n, max(s, lo) - lo, min(e, hi) - lo, line) for n, s, e, line in ops
                   if e > lo and s < hi]
        sync = [(n, s, e) for n, s, e, line in clipped
                if line == "ops" and not T.ASYNC_START.search(n)]
        for n, s, e, _ in clipped:
            if T.COLLECTIVE.match(n):
                collective[s:e] = True
        for n, s, e in sync:
            any_op[s:e] = True
        for n, s, e, _, leaf in T.nest([(n, s, e) for n, s, e in sync]):
            if leaf and not T.COLLECTIVE.match(n):
                compute[s:e] = True
        busy += int(any_op.sum())
        coll += int(collective.sum())
        exposed += int((collective & ~compute).sum())
    k = len(trace.devices)
    assert r.window_s == (hi - lo) / 1e9
    assert r.busy_s == busy / k / 1e9
    assert r.collective_s == coll / k / 1e9
    assert r.collective_exposed_s == exposed / k / 1e9
    assert sum(r.op_s.values()) > 0
