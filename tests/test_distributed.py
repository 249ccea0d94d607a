"""Distributed robust reductions: correctness on a multi-device CPU mesh.

These tests need >1 device, so they run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count set (the main test
process keeps the default 1 device per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


PRELUDE = """
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import distributed, aggregators
from repro.core.attacks import AttackConfig
from repro.launch.mesh import make_mesh
"""

# shard_map with every mesh axis manual, for the collective-batching tests
SMAP = PRELUDE + """
def smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
"""


def test_gather_agg_matches_oracle():
    run_sub(PRELUDE + """
mesh = make_mesh((8,), ("data",))
m = 8
g_all = np.random.default_rng(0).standard_normal((m, 40)).astype(np.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P(),
                   axis_names={"data"}, check_vma=False)
def f(g):
    return distributed.robust_gather_agg({"w": g[0]}, ("data",), "median")["w"]

out = f(jnp.asarray(g_all))
np.testing.assert_allclose(np.asarray(out), np.median(g_all, axis=0), rtol=1e-6)
print("OK")
""")


def test_bucketed_agg_matches_gather_and_oracle():
    run_sub(PRELUDE + """
mesh = make_mesh((8,), ("data",))
m = 8
rng = np.random.default_rng(1)
ga = rng.standard_normal((m, 37)).astype(np.float32)  # odd size -> padding
gb = rng.standard_normal((m, 3, 5)).astype(np.float32)

def mk(strategy):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=P(), axis_names={"data"}, check_vma=False)
    def f(a, b):
        tree = {"a": a[0], "b": b[0]}
        if strategy == "gather":
            out = distributed.robust_gather_agg(tree, ("data",), "median")
        else:
            out = distributed.robust_bucketed_agg(tree, ("data",), "median")
        return out
    return f

for method in ("gather", "bucketed"):
    out = mk(method)(jnp.asarray(ga), jnp.asarray(gb))
    np.testing.assert_allclose(np.asarray(out["a"]), np.median(ga, axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), np.median(gb, axis=0), rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_bucketed_leaf_vs_flat_granularity():
    run_sub(PRELUDE + """
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(9)
ga = rng.standard_normal((8, 37)).astype(np.float32)
gb = rng.standard_normal((8, 3, 5)).astype(np.float32)

def mk(gran):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=P(), axis_names={"data"}, check_vma=False)
    def f(a, b):
        return distributed.robust_bucketed_agg({"a": a[0], "b": b[0]}, ("data",),
                                               "median", granularity=gran)
    return f

for gran in ("leaf", "flat"):
    out = mk(gran)(jnp.asarray(ga), jnp.asarray(gb))
    np.testing.assert_allclose(np.asarray(out["a"]), np.median(ga, axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), np.median(gb, axis=0), rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_bucketed_multi_axis_exact_global_median():
    """pod×data (2×4): bucketed a2a aggregation = global median over all 8
    workers (NOT median-of-medians)."""
    run_sub(PRELUDE + """
mesh = make_mesh((2, 4), ("pod", "data"))
m = 8
g_all = np.random.default_rng(2).standard_normal((m, 26)).astype(np.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
                   axis_names={"pod", "data"}, check_vma=False)
def f(g):
    return distributed.robust_bucketed_agg({"w": g[0]}, ("pod", "data"), "median")["w"]

out = f(jnp.asarray(g_all))
np.testing.assert_allclose(np.asarray(out), np.median(g_all, axis=0), rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_hierarchical_median_of_medians():
    """Hierarchical (pod-local median, then cross-pod median) is a
    DIFFERENT estimator from the global median — verify it equals the
    explicit two-level oracle, not the global one."""
    run_sub(PRELUDE + """
mesh = make_mesh((2, 4), ("pod", "data"))
g_all = np.random.default_rng(11).standard_normal((8, 12)).astype(np.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
                   axis_names={"pod", "data"}, check_vma=False)
def f(g):
    return distributed.robust_hierarchical_agg({"w": g[0]}, "data", "pod", "median")["w"]

out = np.asarray(f(jnp.asarray(g_all)))
# oracle: median within each pod (rows 0-3, 4-7), then median across pods
pod_meds = np.stack([np.median(g_all[:4], axis=0), np.median(g_all[4:], axis=0)])
want = np.median(pod_meds, axis=0)
np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_gradient_attack_applied_at_aggregation():
    """Byzantine rows injected at the aggregation point: mean breaks,
    median survives."""
    run_sub(PRELUDE + """
mesh = make_mesh((8,), ("data",))
g_all = np.ones((8, 16), np.float32)
atk = AttackConfig("large_value", alpha=0.25, scale=1e6)

def mk(method):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P(),
                       axis_names={"data"}, check_vma=False)
    def f(g):
        return distributed.robust_gather_agg({"w": g[0]}, ("data",), method, attack=atk)["w"]
    return f

med = np.asarray(mk("median")(jnp.asarray(g_all)))
mean = np.asarray(mk("mean")(jnp.asarray(g_all)))
assert (np.abs(med - 1.0) < 1e-5).all(), med
assert (mean > 1e4).all(), mean
print("OK")
""")


def test_trimmed_mean_distributed():
    run_sub(PRELUDE + """
mesh = make_mesh((8,), ("data",))
g_all = np.random.default_rng(3).standard_normal((8, 33)).astype(np.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P(),
                   axis_names={"data"}, check_vma=False)
def f(g):
    return distributed.robust_bucketed_agg({"w": g[0]}, ("data",), "trimmed_mean", beta=0.25)["w"]

out = np.asarray(f(jnp.asarray(g_all)))
want = np.sort(g_all, axis=0)[2:6].mean(0)
np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_robust_param_gather_fsdp_bwd():
    """custom_vjp param gather: forward = all-gather; backward = robust
    reduce-scatter (exact coordinate-wise median of per-worker grads)."""
    run_sub(PRELUDE + """
mesh = make_mesh((4,), ("data",))
m = 4
w_full = np.random.default_rng(4).standard_normal((8, 3)).astype(np.float32)
x_all = np.random.default_rng(5).standard_normal((m, 6, 8)).astype(np.float32)

gather = distributed.make_robust_param_gather(("data",), "median")

@functools.partial(jax.shard_map, mesh=mesh,
                   in_specs=(P("data"), P("data")), out_specs=P("data"),
                   axis_names={"data"}, check_vma=False)
def step(w_shard, x):
    def loss(ws):
        w = gather(ws)
        return jnp.sum((x[0] @ w) ** 2)
    g = jax.grad(loss)(w_shard)
    return g

w_sharded = jnp.asarray(w_full)  # (8,3): 2 rows per worker
g_shards = step(w_sharded, jnp.asarray(x_all))  # (8,3) = concat of per-worker buckets

# oracle: per-worker full gradient, coordinate-wise median, then scatter
def full_grad(x):
    return 2 * x.T @ (x @ w_full)
grads = np.stack([full_grad(x_all[i]) for i in range(m)])
want = np.median(grads, axis=0)
np.testing.assert_allclose(np.asarray(g_shards), want, rtol=1e-4, atol=1e-5)
print("OK")
""")


def test_end_to_end_train_step_robustness():
    """Full production train step on a 4x2 debug mesh: median training
    stays stable under a sign-flip Byzantine worker while mean training
    diverges from the clean trajectory."""
    run_sub(PRELUDE + """
from repro.configs import get_smoke_config, ParallelConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.data.pipeline import DataConfig, make_lm_batch, host_to_mesh
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("llama3.2-3b")
mesh = make_debug_mesh(4, 2)
atk = AttackConfig("sign_flip", alpha=0.25, scale=5.0)
dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=32, global_batch=8, num_workers=4)

def train(agg_method, attack, steps_n=8):
    pcfg = ParallelConfig(agg_method=agg_method, agg_strategy="gather", remat=False, attn_chunk=0)
    opt = get_optimizer("adamw", 2e-3)
    with jax.set_mesh(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        pshard = steps.param_shardings(cfg, mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
        state = opt.init(params)
        fn = steps.make_train_step(cfg, pcfg, mesh, opt, attack)
        losses = []
        for i in range(steps_n):
            batch = host_to_mesh(make_lm_batch(dcfg, i), mesh, ("data",))
            params, state, metrics = fn(params, state, batch, jnp.int32(i))
            losses.append(float(metrics["loss"]))
    return losses

clean = train("mean", None)
med_atk = train("median", atk)
mean_atk = train("mean", atk)
print("clean", clean[-1], "median+atk", med_atk[-1], "mean+atk", mean_atk[-1])
assert med_atk[-1] < clean[0], (med_atk, clean)          # robust run still learns
assert mean_atk[-1] > med_atk[-1] - 1e-3                  # mean no better than median under attack
assert abs(med_atk[-1] - clean[-1]) < abs(mean_atk[-1] - clean[-1]) + 0.5
print("OK")
""", devices=8)


def test_fsdp_mode_matches_gather_median():
    """param_mode=fsdp (robust reduce-scatter in bwd) produces the exact
    same update as the paper-faithful gather-median, with params/optimizer
    state sharded over workers."""
    run_sub(PRELUDE + """
from repro.configs import get_smoke_config, ParallelConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.data.pipeline import DataConfig, make_lm_batch, host_to_mesh
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("llama3.2-3b")
mesh = make_debug_mesh(4, 2)
dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=32, global_batch=8, num_workers=4)
opt = get_optimizer("adamw", 1e-3)
atk = AttackConfig("sign_flip", 0.25, scale=3.0)
results = {}
for mode in ("replicated", "fsdp"):
    pcfg = ParallelConfig(agg_method="median", agg_strategy="gather",
                          param_mode=mode, remat=True, attn_chunk=0)
    with jax.set_mesh(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        shard = (steps.fsdp_param_shardings(cfg, mesh)[0] if mode == "fsdp"
                 else steps.param_shardings(cfg, mesh))
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shard)
        state = opt.init(params)
        fn = steps.make_train_step(cfg, pcfg, mesh, opt, atk)
        batch = host_to_mesh(make_lm_batch(dcfg, 0), mesh, ("data",))
        p2, _, m = fn(params, state, batch, jnp.int32(0))
        results[mode] = (np.asarray(jax.tree.leaves(p2)[0], np.float32), float(m["loss"]))
np.testing.assert_allclose(results["replicated"][0], results["fsdp"][0], rtol=5e-2, atol=5e-4)
assert abs(results["replicated"][1] - results["fsdp"][1]) < 1e-5
print("OK")
""")


def test_bucketed_strategy_in_train_step():
    run_sub(PRELUDE + """
from repro.configs import get_smoke_config, ParallelConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.data.pipeline import DataConfig, make_lm_batch, host_to_mesh
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("granite-moe-1b-a400m")
mesh = make_debug_mesh(4, 2)
dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=16, global_batch=8, num_workers=4)
opt = get_optimizer("sgd", 1e-2)
with jax.set_mesh(mesh):
    pshard = steps.param_shardings(cfg, mesh)
    outs = {}
    for strat in ("gather", "bucketed"):
        # fresh arrays per run: the train step donates params/state
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
        state = opt.init(params)
        pcfg = ParallelConfig(agg_method="median", agg_strategy=strat, remat=False, attn_chunk=0)
        fn = steps.make_train_step(cfg, pcfg, mesh, opt, None)
        batch = host_to_mesh(make_lm_batch(dcfg, 0), mesh, ("data",))
        p2, _, m = fn(params, state, batch, jnp.int32(0))
        outs[strat] = (jax.tree.leaves(p2)[0], float(m["loss"]))
# identical estimator -> identical update
np.testing.assert_allclose(np.asarray(outs["gather"][0], np.float32),
                           np.asarray(outs["bucketed"][0], np.float32), rtol=2e-2, atol=1e-4)
assert abs(outs["gather"][1] - outs["bucketed"][1]) < 1e-4
print("OK")
""", devices=8)


def test_bucketed_leaf_coalescing_collective_count():
    """granularity='leaf' coalesces same-size-bin leaves into super-buckets:
    a pytree of 8 leaves in 2 size bins must launch 2 all_to_all + 2
    all_gather pairs (O(#size-bins)), not one pair per leaf — asserted by
    counting collective eqns in the traced jaxpr."""
    run_sub(SMAP + """
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(7)
# 6 leaves of size 40 (one log2 bin) + 2 leaves of size 300 (another bin)
shapes = [(40,)] * 6 + [(300,), (30, 10)]
gs = [jnp.asarray(rng.standard_normal((8,) + s), jnp.float32) for s in shapes]

def body(*args):
    tree = {f"l{i}": a[0] for i, a in enumerate(args)}
    return distributed.robust_bucketed_agg(tree, ("data",), "median")

f = smap(body, mesh, tuple(P("data") for _ in gs), P())
jaxpr = str(jax.make_jaxpr(f)(*gs))
n_a2a = jaxpr.count("all_to_all[")
n_ag = jaxpr.count("all_gather[")
assert n_a2a == 2, f"expected 2 size-bin all_to_alls, got {n_a2a}"
assert n_ag == 2, f"expected 2 size-bin all_gathers, got {n_ag}"

# same story in the compiled HLO, via the launch/hlo_analysis parser
# (XLA's collective combiner may merge further, never split)
from repro.launch import hlo_analysis
txt = jax.jit(f).lower(*gs).compile().as_text()
comps = hlo_analysis.parse_module(txt)
seen = set()
n_hlo = 0
for name, comp in comps.items():
    if name == "__entry__" or name in seen:
        continue
    seen.add(name)
    n_hlo += sum(1 for op in comp.ops if op.opcode.startswith("all-to-all"))
assert 1 <= n_hlo <= 2, f"compiled all-to-all count {n_hlo} not O(#size-bins)"

# and the coalesced result is still the exact global median per leaf
out = f(*gs)
for i, g in enumerate(gs):
    np.testing.assert_allclose(np.asarray(out[f"l{i}"]),
                               np.median(np.asarray(g), axis=0),
                               rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_bucketed_leaf_coalescing_respects_size_cap():
    """Leaves whose combined size exceeds the super-bucket cap split into
    multiple groups — the coalescer must not reintroduce the unbounded
    flat concat."""
    run_sub(SMAP + """
from repro.core.distributed import _coalesce_groups
leaves = [jnp.zeros((1000,)) for _ in range(5)]
groups = _coalesce_groups(leaves, max_elems=2100)
assert [len(g) for g in groups] == [2, 2, 1], groups
assert sorted(i for g in groups for i in g) == list(range(5))
# zero groups never share leaves across dtype bins
mixed = [jnp.zeros((8,), jnp.float32), jnp.zeros((8,), jnp.bfloat16)]
assert len(_coalesce_groups(mixed)) == 2
print("OK")
""")


def test_chunked_agg_single_psum_per_chunk_and_scan():
    """The chunked strategy must issue ONE fused psum per chunk (counts and
    sums concatenated) from inside a lax.scan — trace size O(1) in the
    chunk count."""
    run_sub(SMAP + """
g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 100)), jnp.float32)
mesh = make_mesh((8,), ("data",))

for method, psums in (("median", 1), ("trimmed_mean", 1)):
    def body(gg, method=method):
        return distributed.robust_chunked_agg({"w": gg[0]}, ("data",), method,
                                              beta=0.25, nbins=256,
                                              coord_chunk=16)["w"]
    f = smap(body, mesh, P("data"), P())
    jaxpr = str(jax.make_jaxpr(f)(g))
    assert "scan" in jaxpr, "chunk loop must be a lax.scan"
    n_psum = jaxpr.count("psum")
    assert n_psum == psums, (method, n_psum, psums)

# correctness: sketch median within one bin width of the exact median
f = smap(lambda gg: distributed.robust_chunked_agg(
    {"w": gg[0]}, ("data",), "median", nbins=512, coord_chunk=16)["w"],
    mesh, P("data"), P())
got = np.asarray(f(g))
want = np.median(np.asarray(g), axis=0)
width = (np.asarray(g).max(0) - np.asarray(g).min(0)) / 512
assert (np.abs(got - want) <= width + 1e-6).all()

# trimmed mean too (padding path: 100 coords, chunk 16 -> pad to 112)
ft = smap(lambda gg: distributed.robust_chunked_agg(
    {"w": gg[0]}, ("data",), "trimmed_mean", beta=0.25, nbins=512,
    coord_chunk=16)["w"], mesh, P("data"), P())
got = np.asarray(ft(g))
want = np.sort(np.asarray(g), axis=0)[2:6].mean(0)
assert (np.abs(got - want) <= width + 1e-6).all()
print("OK")
""")


def test_bucketed_coalesced_attack_parity_with_gather():
    """Gradient-space attacks are row-broadcast formulas, so coalescing
    leaves into super-buckets must not change the attacked estimator:
    bucketed(leaf) == gather for a multi-leaf tree under sign_flip."""
    run_sub(SMAP + """
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(3)
shapes = [(11,), (11,), (4, 3), (64,)]
gs = [jnp.asarray(rng.standard_normal((8,) + s), jnp.float32) for s in shapes]
atk = AttackConfig("sign_flip", alpha=0.25, scale=5.0)

def mk(strategy):
    def body(*args):
        tree = {f"l{i}": a[0] for i, a in enumerate(args)}
        if strategy == "gather":
            return distributed.robust_gather_agg(tree, ("data",), "median",
                                                 attack=atk)
        return distributed.robust_bucketed_agg(tree, ("data",), "median",
                                               attack=atk)
    return smap(body, mesh, tuple(P("data") for _ in gs), P())

oa, og = mk("bucketed")(*gs), mk("gather")(*gs)
for k in oa:
    np.testing.assert_allclose(np.asarray(oa[k]), np.asarray(og[k]),
                               rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_psum_agg_plain_mean_baseline():
    """The psum strategy (the throughput-gate baseline) must be an EXACT
    mean — one all-reduce per leaf, attacks simulated row-free like the
    chunked strategy — and must reject any order-statistic method (a
    psum cannot compute a median; failing loudly keeps the baseline
    honest)."""
    run_sub(SMAP + """
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(7)
ga = rng.standard_normal((8, 37)).astype(np.float32)
gb = rng.standard_normal((8, 3, 5)).astype(np.float32)

def mk(attack=None):
    def body(a, b):
        return distributed.robust_psum_agg({"a": a[0], "b": b[0]}, ("data",),
                                           "mean", attack=attack)
    return smap(body, mesh, (P("data"), P("data")), P())

out = mk()(jnp.asarray(ga), jnp.asarray(gb))
np.testing.assert_allclose(np.asarray(out["a"]), ga.mean(0), rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(out["b"]), gb.mean(0), rtol=1e-5, atol=1e-6)

# the attack flows through the all-reduce undefended (that's the point
# of the baseline): sign_flip shifts the mean, and matches the oracle
# computed from the same row-free formula via the gather strategy
atk = AttackConfig("sign_flip", alpha=0.25, scale=5.0)
oa = mk(atk)(jnp.asarray(ga), jnp.asarray(gb))
assert not np.allclose(np.asarray(oa["a"]), ga.mean(0))

def gather_mean(a, b):
    return distributed.robust_gather_agg({"a": a[0], "b": b[0]}, ("data",),
                                         "mean", attack=atk)
og = smap(gather_mean, mesh, (P("data"), P("data")), P())(
    jnp.asarray(ga), jnp.asarray(gb))
for k in oa:
    np.testing.assert_allclose(np.asarray(oa[k]), np.asarray(og[k]),
                               rtol=1e-5, atol=1e-6)

# exactly one all-reduce (psum) per leaf, no gathers
jaxpr = str(jax.make_jaxpr(mk())(jnp.asarray(ga), jnp.asarray(gb)))
assert jaxpr.count("psum") == 2, jaxpr.count("psum")
assert "all_gather" not in jaxpr and "all_to_all" not in jaxpr
print("OK")
""")


def test_psum_agg_rejects_order_statistics():
    import jax.numpy as jnp
    import pytest as _pytest

    from repro.core import distributed as dist

    with _pytest.raises(ValueError, match="plain data-parallel"):
        dist.robust_psum_agg({"w": jnp.ones((4,))}, ("data",), "median")
