"""Device time by scope (``scopes.py``) on small timelines with known
answers, against a brute-force count on random ones, and on an excerpt of
a chip trace; and ``trace.py``'s reduction of the recorded excerpt it was
checked on, unchanged."""
import gzip
import json
import random

import numpy as np
import pytest

from chip_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import scopes as S
from benchmarks.chip import trace as T

W = T.WINDOW
STEP = "jit(window)/while/body/closed_call"
GRAD = f"{STEP}/robust.local_grad"


@pytest.mark.parametrize("path,scope", [
    (f"{GRAD}/transpose(jvp())/checkpoint/rematted_computation/model.attn/dot_general:",
     "model.attn"),
    (f"{GRAD}/jvp(model.head_loss)/reduce_sum:", "model.head_loss"),
    (f"{GRAD}/transpose(jvp(model.embed))/scatter-add:", "model.embed"),
    (f"{STEP}/robust.aggregate/robust.attack/select_n:", "robust.attack"),
    (f"{STEP}/robust.update/mul:", "robust.update"),
    ("jit(lm_batch)/data.lm_batch/while/body/add:", "data.lm_batch"),
    ("jit(_threefry_fold_in)/concatenate:", S.UNSCOPED),
    ("", S.UNSCOPED),
])
def test_scope_is_the_innermost_named_component(path, scope):
    assert S.scope_of(path) == scope


def small_timeline():
    return S.Timeline(
        devices={
            "/device:TPU:0": [
                ("fusion.0", 20, 90, f"{GRAD}/model.attn/dot:"),  # before the window
                ("while.1", 100, 700, f"{GRAD}/while:"),  # holds the next three
                ("fusion.2", 150, 400, f"{GRAD}/model.mlp/dot:"),
                ("copy-start.3", 380, 390, f"{GRAD}/copy-start:"),  # in flight, not busy
                ("fusion.4", 400, 600,
                 f"{GRAD}/transpose(jvp())/checkpoint/rematted_computation/model.attn/dot:"),
                ("fusion.5", 800, 900, f"{STEP}/robust.update/mul:"),
                ("add.6", 900, 950, f"{STEP}/add:"),
                ("fusion.7", 1000, 1200, "jit(lm_batch)/data.lm_batch/add:"),  # ends after
            ],
            "/device:TPU:1": [("fusion.1", 100, 1100, f"{STEP}/robust.aggregate/robust.attack/x:")],
        },
        host=[
            (W, 100, 1100, "main"),
            ("train.window", 50, 760, "main"),  # starts before the window
            ("train.dispatch", 120, 140, "main"),
            ("train.metric_read", 760, 990, "main"),
            ("train.stage_batches", 990, 1060, "main"),
            ("np.asarray(jax.Array)", 770, 800, "main"),  # a runtime event
            ("train.checkpoint", 0, 2000, "other"),  # another thread
        ],
    )


def test_small_timeline_exactly():
    r = S.reduce(small_timeline())
    assert r.window_s == 1000 / 1e9
    # device 0: busy 100-700, 800-950, 1000-1100 = 850 ns; device 1: 1000 ns
    assert r.busy_s == 925 / 1e9
    assert r.scope_s == {"robust.local_grad": 75 / 1e9, "model.mlp": 125 / 1e9,
                         "model.attn": 100 / 1e9, "robust.update": 50 / 1e9,
                         S.UNSCOPED: 25 / 1e9, "data.lm_batch": 50 / 1e9,
                         "robust.attack": 500 / 1e9}
    assert sum(r.scope_s.values()) == pytest.approx(r.busy_s, rel=1e-12)
    assert r.remat_s == 100 / 1e9
    assert r.host_s == {"train.window": 660 / 1e9, "train.dispatch": 20 / 1e9,
                        "train.metric_read": 230 / 1e9, "train.stage_batches": 70 / 1e9}
    assert r.host_n == {"train.window": 1, "train.dispatch": 1, "train.metric_read": 1,
                        "train.stage_batches": 1}
    # device 0 idles 700-800 and 950-1000: under the window span alone
    # 700-760, under the read 760-800 and 950-990, under staging 990-1000
    assert r.idle_s == {"train.window": 30 / 1e9, "train.metric_read": 40 / 1e9,
                        "train.stage_batches": 5 / 1e9}
    layers = S.per_layer(r, steps=2)
    assert layers == pytest.approx({
        "local_grad_ms_per_step": 1e3 * 300 / 1e9 / 2, "update_ms_per_step": 1e3 * 50 / 1e9 / 2,
        "robust_ms_per_step": 1e3 * 500 / 1e9 / 2, "data_ms_per_step": 1e3 * 50 / 1e9 / 2,
        "host_stage_ms_per_window": 1e3 * 70 / 1e9}, rel=1e-12)


def test_table_lists_each_scope_and_span():
    r = S.reduce(small_timeline())
    lines = S.table(r).splitlines()
    for name in list(r.scope_s) + list(r.host_s):
        assert sum(line.split()[0] == name for line in lines) == 1, name
    assert [line.split()[1] for line in lines if line.startswith("sum ")] == [f"{r.busy_s:.6f}"]


def test_a_program_without_spans_gives_no_layer():
    tl = small_timeline()
    tl.devices = {d: [(n, s, e, "jit(window)/fusion:") for n, s, e, _ in ops]
                  for d, ops in tl.devices.items()}
    tl.host = [h for h in tl.host if h[0] == W]
    r = S.reduce(tl)
    assert r.scope_s == {S.UNSCOPED: r.busy_s} and r.host_s == {}
    assert r.idle_s == {S.NO_SPAN: 75 / 1e9}
    assert S.per_layer(r, steps=2) == {}


def test_only_listed_devices_count():
    r = S.reduce(small_timeline(), devices=["/device:TPU:1"])
    assert r.busy_s == 1000 / 1e9 and r.scope_s == {"robust.attack": 1000 / 1e9}


PATHS = [f"{GRAD}/model.attn/a:", f"{GRAD}/transpose(jvp(model.mlp))/b:",
         f"{STEP}/robust.aggregate/robust.attack/c:", f"{STEP}/robust.update/d:",
         "jit(lm_batch)/data.lm_batch/e:", f"{STEP}/f:",
         f"{GRAD}/checkpoint/rematted_computation/model.attn/g:"]


def _laminar(rng, lo, hi, depth, out):
    """Random ops nested like an XLA loop's: disjoint siblings, each
    possibly holding more."""
    t = lo
    while t < hi - 2:
        s = t + rng.randrange(0, 8)
        e = min(hi, s + rng.randrange(2, 60))
        if e - s < 2:
            break
        name = rng.choice(["fusion", "while", "copy-start", "add"]) + f".{len(out)}"
        out.append((name, s, e, rng.choice(PATHS)))
        if depth and rng.random() < 0.4:
            _laminar(rng, s + 1, e - 1, depth - 1, out)
        t = e
    return out


def _paint(ops, spans, lo, hi):
    """Per scope, the nanoseconds in which its op is the innermost running;
    the remat nanoseconds; and per host span, the idle nanoseconds in which
    it is the innermost span: one nanosecond at a time."""
    sync = [(s, e, p) for n, s, e, p in ops if not T.ASYNC_START.search(n)]
    by_scope, remat, idle = {}, 0, {}
    for t in range(lo, hi):
        running = [(e - s, p) for s, e, p in sync if s <= t < e]
        if running:
            path = min(running)[1]
            by_scope[S.scope_of(path)] = by_scope.get(S.scope_of(path), 0) + 1
            remat += S.REMAT in path.split("/")
        else:
            holding = [(e - s, n) for n, s, e in spans if s <= t < e]
            name = min(holding)[1] if holding else S.NO_SPAN
            idle[name] = idle.get(name, 0) + 1
    return by_scope, remat, idle


@pytest.mark.parametrize("seed", range(5))
def test_random_timelines_match_brute_force(seed):
    rng = random.Random(seed)
    ops = _laminar(rng, 0, 600, 3, [])
    spans = [(rng.choice(["train.window", "train.stage", "train.read"]), s, e)
             for _, s, e, _ in _laminar(rng, 0, 600, 2, [])]
    lo, hi = 40, 520
    tl = S.Timeline(devices={"/device:TPU:0": ops},
                    host=[(W, lo, hi, "main")] + [(n, s, e, "main") for n, s, e in spans])
    r = S.reduce(tl)
    by_scope, remat, idle = _paint(ops, [sp for sp in spans if sp[2] > lo and sp[1] < hi],
                                   lo, hi)
    assert {k: round(v * 1e9) for k, v in r.scope_s.items() if round(v * 1e9)} == by_scope
    assert round(r.remat_s * 1e9) == remat
    assert {k: round(v * 1e9) for k, v in r.idle_s.items()} == idle
    assert round(r.busy_s * 1e9) == sum(by_scope.values())
    # the busy time is the one trace.py reads
    trace = T.Trace(devices={"/device:TPU:0": [(n, s, e, "ops") for n, s, e, _ in ops]},
                    host=tl.host)
    assert r.busy_s == T.reduce(trace).busy_s


def test_chrome_trace_is_read_in_pieces(tmp_path):
    """``load`` streams a trace larger than its read size and keeps the
    ops line of each device and the host's events."""
    meta = [{"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 9, "name": "process_name", "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 9, "tid": 7, "name": "thread_name", "args": {"name": "python3"}}]
    ops = [{"ph": "X", "pid": 3, "tid": 3, "ts": i * 0.01, "dur": 0.005, "name": f"fusion.{i}",
            "args": {"tf_op": PATHS[i % len(PATHS)], "long_name": "x" * 40}}
           for i in range(30000)]
    other = [{"ph": "X", "pid": 3, "tid": 2, "ts": 0.0, "dur": 300.0, "name": "jit(window)"},
             {"ph": "X", "pid": 9, "tid": 7, "ts": 0.5, "dur": 299.0, "name": W}]
    path = tmp_path / "h.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"displayTimeUnit": "ns", "traceEvents": meta + ops + other}, fh)
    assert path.stat().st_size < 2**20 < len(gzip.decompress(path.read_bytes()))
    tl = S.load(str(path))
    assert list(tl.devices) == ["/device:TPU:0"]
    assert tl.devices["/device:TPU:0"][:2] == [("fusion.0", 0, 5, PATHS[0]),
                                               ("fusion.1", 10, 15, PATHS[1])]
    assert len(tl.devices["/device:TPU:0"]) == 30000
    assert tl.host == [(W, 500, 299500, "python3")]
    r = S.read(str(tmp_path))
    assert r.busy_s == (29950 - 50) * 5 / 1e9


def _excerpt():
    data = json.loads((ROOT / "benchmarks/chip/tests/data/trace_excerpt.json").read_text())
    return data, T.Trace(devices={d: [tuple(e) for e in ev] for d, ev in data["devices"].items()},
                         host=[tuple(e) for e in data["host"]])


def test_recorded_excerpt_reduces_as_before():
    """``trace.py``'s numbers for the excerpt of the first chip trace, as
    they read before the program named its layers."""
    _, trace = _excerpt()
    r = T.reduce(trace)
    assert (r.window_s, r.busy_s, r.collective_s, r.collective_exposed_s) == (0.005, 0.005, 0, 0)
    assert len(r.op_s) == 42 and sum(r.op_s.values()) == pytest.approx(0.005, rel=1e-12)
    assert T.top_ops(r, 3) == [("fusion.641", 0.003179718), ("fusion.635", 0.00065077),
                               ("fusion.642", 0.000562841)]
    assert r.idle_gaps == []


def test_recorded_scoped_excerpt_matches_a_timeline():
    """Eight milliseconds of a chip trace of the scoped program (the end of
    one window step, the host's wait and read, the next window's staging
    up to its first ``lm_batch`` ops), reduced by ``scopes.py`` and by
    giving each stretch between op boundaries to the shortest op running
    in it: the two agree exactly."""
    data = json.loads((ROOT / "benchmarks/chip/tests/data/scopes_excerpt.json").read_text())
    tl = S.Timeline(devices={d: [tuple(e) for e in ev] for d, ev in data["devices"].items()},
                    host=[tuple(e) for e in data["host"]])
    r = S.reduce(tl)
    lo, hi = data["window"]
    (ops,) = tl.devices.values()
    ops = [(max(s, lo), min(e, hi), p) for n, s, e, p in ops
           if e > lo and s < hi and not T.ASYNC_START.search(n)]
    start, end = np.array([o[0] for o in ops]), np.array([o[1] for o in ops])
    cuts = np.unique(np.concatenate([start, end]))
    painted, busy = {}, 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        running = (start <= a) & (end >= b)
        if running.any():
            i = int(np.argmin(np.where(running, end - start, np.iinfo(np.int64).max)))
            scope = S.scope_of(ops[i][2])
            painted[scope] = painted.get(scope, 0) + int(b - a)
            busy += int(b - a)
    assert {k: round(v * 1e9) for k, v in r.scope_s.items() if round(v * 1e9)} == painted
    assert round(r.busy_s * 1e9) == busy
    assert sorted(painted) == data["scopes"]
    assert sorted(r.host_s) == data["host_spans"]
    # the idle stretches, each to the shortest host span holding it
    spans = [(n, s_, e) for n, s_, e, t in tl.host if S.SPAN.fullmatch(n)]
    idle = {}
    for g0, g1 in T.subtract([(lo, hi)], T.union([(a, b) for a, b, _ in ops])):
        edges = sorted({g0, g1} | {t for _, s_, e in spans for t in (s_, e) if g0 < t < g1})
        for a, b in zip(edges, edges[1:]):
            holding = [(e - s_, n) for n, s_, e in spans if s_ <= a and b <= e]
            name = min(holding)[1] if holding else S.NO_SPAN
            idle[name] = idle.get(name, 0) + b - a
    assert {k: round(v * 1e9) for k, v in r.idle_s.items()} == idle
    assert sum(idle.values()) + busy == hi - lo
