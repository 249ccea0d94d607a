"""Differential harness pinning the unified round engine bit-for-bit.

``rounds.engine`` replaced three hand-rolled loops — the Algorithm 1 scan
in ``core.robust_gd``, the τ-interpolation scan + scheduled host loop in
``rounds.local_update``, and the federated server loop in ``fed.rounds``.
This file keeps FROZEN copies of the legacy loop skeletons (transplanted
verbatim from the pre-engine revisions; the per-round helpers they call —
``_round_deltas``, ``_compress_deltas``, ``aggregate_cohort``, ... — are
unchanged and imported) and asserts the engine-backed wrappers reproduce
them **bit-for-bit**: ``tobytes()`` equality on the final iterate, every
stacked metric, and every host-history float.  Tolerance-based comparison
would hide exactly the class of bug this harness exists to catch (a
reordered reduction, a different key fold, a stage run out of order).

The second half is the checkpoint/resume contract: kill a run at ANY
round boundary, resume from the snapshot, and the final state — iterate,
error-feedback residuals, optimizer state, greedy-scheduler picks — must
be bit-identical to the uninterrupted run.  Covered for the scan driver
(both its eager and jitted regimes), the scheduled driver, the federated
sync loop and the buffered-async loop.

``hypothesis`` is optional: without it the property test skips and every
plain test still collects and runs (the seed container does not ship
hypothesis).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip, unit tests still run
    def given(*_args, **_kwargs):
        return lambda fn: pytest.mark.skip(reason="hypothesis not installed")(fn)

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    class _StrategyStub:
        """Absorbs strategy construction at decoration time."""

        def __getattr__(self, _name):
            return lambda *a, **k: _StrategyStub()

        def __call__(self, *a, **k):
            return _StrategyStub()

    st = _StrategyStub()

from repro.core import aggregators
from repro.core.attacks import AttackConfig, apply_gradient_attack
from repro.core.robust_gd import (
    RobustGDConfig,
    _project,
    linreg_loss,
    make_worker_shards,
    robust_gd,
)
from repro.fed.population import ArrivalConfig, ClientPopulation, PopulationConfig
from repro.fed.rounds import (
    AttackMixture,
    RoundConfig,
    aggregate_cohort,
    init_comp_residual,
    run_rounds,
    update_comp_residual,
)
from repro.fed.async_rounds import AsyncConfig, run_async_rounds
from repro.optim.optimizers import get_optimizer
from repro.rounds import LocalUpdateConfig, engine, local_update_gd
from repro.rounds.local_update import (
    _attack_deltas,
    _compress_deltas,
    _init_comp_state,
    _round_deltas,
    make_local_update_stages,
    run_local_update_rounds,
)
from repro.rounds import comm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bits(tree) -> bytes:
    """Concatenated raw bytes of every leaf — the bit-for-bit identity."""
    return b"".join(np.asarray(l).tobytes() for l in jax.tree.leaves(tree))


def assert_bitequal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), (
            f"{msg}: max abs diff "
            f"{np.max(np.abs(np.asarray(x) - np.asarray(y)))}")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# Frozen legacy loops (pre-engine revisions, loop skeletons verbatim)
# ---------------------------------------------------------------------------


def legacy_robust_gd(loss_fn, w0, worker_data, cfg, attack=None,
                     trajectory_fn=None):
    """core.robust_gd.robust_gd as it was before the engine port."""
    m = jax.tree.leaves(worker_data)[0].shape[0]
    grad_fn = jax.grad(loss_fn)
    per_worker_grads = jax.vmap(grad_fn, in_axes=(None, 0))
    agg = aggregators.get_aggregator(cfg.method, cfg.beta)
    mask = attack.byzantine_mask(m) if attack is not None else jnp.zeros((m,), bool)
    attacking = attack is not None and attack.alpha > 0
    base_key = jax.random.PRNGKey(0)

    def step(carry, i):
        w, prev_g = carry
        grads = per_worker_grads(w, worker_data)
        if attacking:
            k = jax.random.fold_in(base_key, i)
            grads = jax.tree.map(
                lambda g, p: apply_gradient_attack(
                    attack, g, mask, key=k, prev_agg=p, rnd=i),
                grads, prev_g)
        g = jax.tree.map(agg, grads)
        w_new = jax.tree.map(lambda p, d: p - cfg.step_size * d, w, g)
        w_new = _project(w_new, cfg.projection_radius)
        metric = trajectory_fn(w_new) if trajectory_fn is not None else jnp.float32(0)
        return (w_new, g), metric

    prev0 = jax.tree.map(jnp.zeros_like, w0)
    (w_final, _), metrics = jax.lax.scan(
        step, (w0, prev0), jnp.arange(cfg.num_iters))
    return w_final, metrics


def legacy_local_update_gd(loss_fn, w0, worker_data, cfg, attack=None,
                           trajectory_fn=None):
    """rounds.local_update.local_update_gd's pre-engine scan (the round
    helpers it calls are shared with the engine stages — the frozen part
    is the (w, prev_d, res) carry skeleton)."""
    m = jax.tree.leaves(worker_data)[0].shape[0]
    grad_fn = jax.grad(loss_fn)
    grads_shared = jax.vmap(grad_fn, in_axes=(None, 0))
    grads_local = jax.vmap(grad_fn, in_axes=(0, 0))
    agg = aggregators.get_aggregator(cfg.method, cfg.beta)
    spec, alpha, strength = comm.resolve_attack_checked(attack)
    attacking = spec is not None and alpha > 0
    eta = cfg.step_size

    def round_step(carry, r):
        w, prev_d, res = carry
        deltas = _round_deltas(grads_shared, grads_local, w, worker_data,
                               cfg.tau, eta)
        deltas, res = _compress_deltas(deltas, res, cfg.compression, r)
        if attacking:
            deltas = _attack_deltas(deltas, prev_d, spec, alpha, strength, m, r)
        d_agg = jax.tree.map(agg, deltas)
        w_new = jax.tree.map(lambda p, dd: p - eta * dd, w, d_agg)
        w_new = _project(w_new, cfg.projection_radius)
        metric = trajectory_fn(w_new) if trajectory_fn is not None else jnp.float32(0)
        return (w_new, d_agg, res), metric

    prev0 = jax.tree.map(jnp.zeros_like, w0)
    res0 = _init_comp_state(cfg.compression, w0, m)
    (w_final, _, res_final), metrics = jax.lax.scan(
        round_step, (w0, prev0, res0), jnp.arange(cfg.num_rounds))
    return w_final, metrics, res_final


def legacy_run_local_update_rounds(loss_fn, w0, worker_data, cfg,
                                   mixture=None, trajectory_fn=None):
    """rounds.local_update.run_local_update_rounds' pre-engine host loop
    (per-attack jit cache, host-side metric/damage, greedy feedback)."""
    scheduler = mixture.make_scheduler() if mixture is not None else None
    m = jax.tree.leaves(worker_data)[0].shape[0]
    grad_fn = jax.grad(loss_fn)
    grads_shared = jax.vmap(grad_fn, in_axes=(None, 0))
    grads_local = jax.vmap(grad_fn, in_axes=(0, 0))
    agg = aggregators.get_aggregator(cfg.method, cfg.beta)
    eta = cfg.step_size
    round_fns = {}

    def get_round_fn(attack):
        spec, alpha, strength = comm.resolve_attack_checked(attack)
        key = (None if spec is None else spec.name, alpha, strength)
        if key not in round_fns:
            @jax.jit
            def round_fn(w, prev_d, res, r):
                deltas = _round_deltas(grads_shared, grads_local, w,
                                       worker_data, cfg.tau, eta)
                deltas, res = _compress_deltas(deltas, res, cfg.compression, r)
                if spec is not None and alpha > 0:
                    deltas = _attack_deltas(deltas, prev_d, spec, alpha,
                                            strength, m, r)
                d_agg = jax.tree.map(agg, deltas)
                w_new = jax.tree.map(lambda p, dd: p - eta * dd, w, d_agg)
                return _project(w_new, cfg.projection_radius), d_agg, res

            round_fns[key] = round_fn
        return round_fns[key]

    w = w0
    history = []
    prev_metric = float(trajectory_fn(w)) if trajectory_fn is not None else 0.0
    prev_d = jax.tree.map(jnp.zeros_like, w0)
    comp_res = _init_comp_state(cfg.compression, w0, m)
    for r in range(cfg.num_rounds):
        attack = mixture.for_round(r, scheduler) if mixture is not None else None
        w, d_agg, comp_res = get_round_fn(attack)(w, prev_d, comp_res,
                                                  jnp.int32(r))
        metric = float(trajectory_fn(w)) if trajectory_fn is not None else 0.0
        d_norm = float(jnp.linalg.norm(
            jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(d_agg)])))
        if scheduler is not None:
            damage = (metric - prev_metric) if trajectory_fn is not None else d_norm
            scheduler.feedback(r, damage)
        prev_metric = metric
        prev_d = d_agg
        history.append({
            "round": r,
            "attack": attack.name if attack is not None else "none",
            "tau": cfg.tau,
            "delta_norm": d_norm,
            "metric": metric,
        })
    return w, history


def legacy_run_rounds(pop, rcfg, mixture=AttackMixture(), w0=None):
    """fed.rounds.run_rounds' pre-engine server loop (aggregate_cohort /
    update_comp_residual are shared with the engine body and imported)."""
    opt = get_optimizer(rcfg.optimizer, rcfg.lr)
    w = jnp.zeros((pop.cfg.dim,)) if w0 is None else w0
    state = opt.init(w)
    root = jax.random.PRNGKey(rcfg.seed)
    scheduler = mixture.make_scheduler()
    history = []
    prev_g = None
    prev_err = float(jnp.linalg.norm(w - pop.w_star))
    comp_res = init_comp_residual(pop, rcfg)
    for r in range(rcfg.num_rounds):
        attack = mixture.for_round(r, scheduler)
        ids = pop.sample_cohort(jax.random.fold_in(root, r), rcfg.cohort_size)
        g = aggregate_cohort(pop, w, ids, rcfg, attack, prev_agg=prev_g, rnd=r,
                             comp_res=comp_res)
        if comp_res is not None:
            comp_res = update_comp_residual(pop, w, ids, rcfg, comp_res, r)
        prev_g = g
        if rcfg.local_steps > 1:
            g = g / rcfg.local_steps
        w, state = opt.update(g, state, w, jnp.int32(r))
        err = float(jnp.linalg.norm(w - pop.w_star))
        if scheduler is not None:
            scheduler.feedback(r, err - prev_err)
        prev_err = err
        history.append({
            "round": r,
            "attack": attack.name if attack is not None else "none",
            "grad_norm": float(jnp.linalg.norm(g)),
            "err": err,
        })
    return w, history


# ---------------------------------------------------------------------------
# Shared tiny fixtures
# ---------------------------------------------------------------------------


def _linreg(sigma=0.3, n=8, m=8, d=6, seed=0):
    kx, kn, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    N = n * m
    x = jax.random.normal(kx, (N, d))
    w_star = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    y = x @ w_star + sigma * jax.random.normal(kn, (N,))
    return make_worker_shards((x, y), m), w_star


SHARDS, W_STAR = _linreg()
W0 = jnp.zeros((6,))
TRAJ = lambda w: jnp.linalg.norm(w - W_STAR)

ATTACKS = {
    "none": None,
    "alie": AttackConfig("alie", alpha=0.25),
    "sign_flip": AttackConfig("sign_flip", alpha=0.25, scale=8.0),
    "stale": AttackConfig("stale", alpha=0.25),
}


@pytest.fixture(scope="module")
def population():
    return ClientPopulation(PopulationConfig(
        num_clients=64, samples_per_client=8, dim=12, alpha=0.25,
        heterogeneity=0.3, seed=2))


# ---------------------------------------------------------------------------
# Engine ≡ legacy: Algorithm 1 (core.robust_gd)
# ---------------------------------------------------------------------------


class TestRobustGDEquivalence:
    @pytest.mark.parametrize("attack", list(ATTACKS))
    def test_bitwise_vs_legacy(self, attack):
        cfg = RobustGDConfig(method="median", step_size=0.1, num_iters=8)
        w_new, m_new = robust_gd(linreg_loss, W0, SHARDS, cfg,
                                 ATTACKS[attack], TRAJ)
        w_old, m_old = legacy_robust_gd(linreg_loss, W0, SHARDS, cfg,
                                        ATTACKS[attack], TRAJ)
        assert_bitequal(w_new, w_old, f"iterate[{attack}]")
        assert_bitequal(m_new, m_old, f"metrics[{attack}]")

    def test_trimmed_mean_with_projection(self):
        cfg = RobustGDConfig(method="trimmed_mean", beta=0.3, step_size=0.1,
                             num_iters=8, projection_radius=0.8)
        atk = ATTACKS["alie"]
        w_new, m_new = robust_gd(linreg_loss, W0, SHARDS, cfg, atk, TRAJ)
        w_old, m_old = legacy_robust_gd(linreg_loss, W0, SHARDS, cfg, atk, TRAJ)
        assert_bitequal(w_new, w_old)
        assert_bitequal(m_new, m_old)

    def test_caller_w0_survives_engine_donation(self):
        # make_state copies leaves; the donated scan must not invalidate
        # the caller's arrays
        w0 = jnp.ones((6,))
        cfg = RobustGDConfig(num_iters=3)
        robust_gd(linreg_loss, w0, SHARDS, cfg)
        assert float(jnp.sum(w0)) == 6.0  # still alive and unchanged

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16 - 1))
    def test_property_seeded_equivalence(self, seed):
        # property pin: ANY dataset draw + adaptive attack stays bit-equal
        shards, w_star = _linreg(sigma=0.5, n=4, m=6, d=4, seed=seed)
        cfg = RobustGDConfig(method="median", step_size=0.2, num_iters=5)
        atk = AttackConfig("stale", alpha=1 / 3)
        traj = lambda w: jnp.linalg.norm(w - w_star)
        w0 = jnp.zeros((4,))
        w_new, m_new = robust_gd(linreg_loss, w0, shards, cfg, atk, traj)
        w_old, m_old = legacy_robust_gd(linreg_loss, w0, shards, cfg, atk, traj)
        assert_bitequal(w_new, w_old, f"seed={seed}")
        assert_bitequal(m_new, m_old, f"seed={seed}")


# ---------------------------------------------------------------------------
# Engine ≡ legacy: τ-interpolation (rounds.local_update)
# ---------------------------------------------------------------------------


class TestLocalUpdateEquivalence:
    @pytest.mark.parametrize("compression,tau", [
        ("none", 1), ("none", 4), ("int8", 1), ("int8", 4), ("topk", 4),
    ])
    @pytest.mark.parametrize("attack", ["none", "alie", "stale"])
    def test_scan_bitwise_vs_legacy(self, compression, tau, attack):
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=tau,
                                num_rounds=6, compression=compression)
        w_new, m_new = local_update_gd(linreg_loss, W0, SHARDS, cfg,
                                       ATTACKS[attack], TRAJ)
        w_old, m_old, _ = legacy_local_update_gd(linreg_loss, W0, SHARDS, cfg,
                                                 ATTACKS[attack], TRAJ)
        assert_bitequal(w_new, w_old, f"iterate[{compression},{tau},{attack}]")
        assert_bitequal(m_new, m_old, f"metrics[{compression},{tau},{attack}]")

    def test_error_feedback_residual_matches_legacy(self):
        # the engine carries comp_res in RoundState; the final residual
        # must equal the legacy scan carry's
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=2,
                                num_rounds=6, compression="topk")
        atk = ATTACKS["alie"]
        m = jax.tree.leaves(SHARDS)[0].shape[0]
        stages = make_local_update_stages(linreg_loss, SHARDS, cfg, atk, TRAJ)
        state = engine.make_state(
            W0, comp_res=_init_comp_state(cfg.compression, W0, m))
        state, _ = engine.run_scan(stages, state, cfg.num_rounds)
        _, _, res_old = legacy_local_update_gd(linreg_loss, W0, SHARDS, cfg, atk)
        assert_bitequal(state["comp_res"], res_old, "comp_res")

    @pytest.mark.parametrize("schedule", ["cycle", "greedy"])
    def test_scheduled_rounds_bitwise_vs_legacy(self, schedule):
        # the greedy path exercises run_scheduled's damage feedback: one
        # diverging pick would change every later attack AND iterate
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=2,
                                num_rounds=10, compression="int8")
        mixture = AttackMixture(
            (AttackConfig("sign_flip", alpha=0.25, scale=8.0),
             AttackConfig("alie", alpha=0.25),
             AttackConfig("stale", alpha=0.25)),
            schedule=schedule)
        w_new, h_new = run_local_update_rounds(linreg_loss, W0, SHARDS, cfg,
                                               mixture, TRAJ)
        w_old, h_old = legacy_run_local_update_rounds(linreg_loss, W0, SHARDS,
                                                      cfg, mixture, TRAJ)
        assert_bitequal(w_new, w_old, schedule)
        assert h_new == h_old  # exact floats incl. greedy pick sequence


# ---------------------------------------------------------------------------
# Engine ≡ legacy: federated server loop (fed.rounds)
# ---------------------------------------------------------------------------

FED_CONFIGS = {
    "exact_median": dict(method="median"),
    "streaming": dict(method="approx_median", nbins=64),
    "ef_topk": dict(method="median", compression="topk"),
    "int8_tau3": dict(method="median", compression="int8", local_steps=3),
    "trimmed_momentum": dict(method="approx_trimmed_mean", beta=0.25,
                             nbins=64, optimizer="momentum"),
}


class TestFedEquivalence:
    @pytest.mark.parametrize("name", list(FED_CONFIGS))
    def test_bitwise_vs_legacy(self, name, population):
        rcfg = RoundConfig(num_rounds=6, cohort_size=32, chunk_clients=8,
                           lr=0.3, seed=3, **FED_CONFIGS[name])
        mixture = AttackMixture(
            (AttackConfig("sign_flip", alpha=0.25, scale=8.0),
             AttackConfig("alie", alpha=0.25)),
            schedule="cycle")
        w_new, h_new = run_rounds(population, rcfg, mixture)
        w_old, h_old = legacy_run_rounds(population, rcfg, mixture)
        assert_bitequal(w_new, w_old, name)
        assert h_new == h_old

    def test_greedy_adversary_bitwise_vs_legacy(self, population):
        rcfg = RoundConfig(num_rounds=10, cohort_size=32, chunk_clients=8,
                           method="median", lr=0.3, seed=3)
        mixture = AttackMixture(
            (AttackConfig("sign_flip", alpha=0.25, scale=8.0),
             AttackConfig("alie", alpha=0.25),
             AttackConfig("stale", alpha=0.25)),
            schedule="greedy")
        w_new, h_new = run_rounds(population, rcfg, mixture)
        w_old, h_old = legacy_run_rounds(population, rcfg, mixture)
        assert_bitequal(w_new, w_old)
        assert h_new == h_old


# ---------------------------------------------------------------------------
# Strategy axis: shard_map round programs driven by the engine
# ---------------------------------------------------------------------------

STRATEGY_PROG = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.robust_gd import make_worker_shards, linreg_loss
from repro.launch.mesh import make_mesh
from repro.rounds import LocalUpdateConfig, engine, make_local_update_round

mesh = make_mesh((8,), ("data",))
kx, kn, kw = jax.random.split(jax.random.PRNGKey(0), 3)
d, n, m = 6, 8, 8
x = jax.random.normal(kx, (n*m, d))
w_star = jax.random.normal(kw, (d,))/jnp.sqrt(d)
y = x @ w_star + 0.3*jax.random.normal(kn, (n*m,))
shards = make_worker_shards((x, y), m)
w0 = jnp.zeros((d,))

for tau in (1, 4):
    cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=tau,
                            num_rounds=6)
    for strat in ("gather", "bucketed", "chunked"):
        step = make_local_update_round(linreg_loss, cfg, mesh, strategy=strat)
        # legacy: bare python round loop over the jitted round program
        w_ref = w0
        for r in range(cfg.num_rounds):
            w_ref = step(w_ref, shards, jnp.int32(r))
        # engine: the same round program as a scheduled round body
        def round_fn_for(attack, step=step):
            def fn(state, r):
                w_new = step(state["w"], shards, jnp.int32(r))
                return dict(state, w=w_new, round=jnp.int32(r) + 1), None
            return fn
        state, _ = engine.run_scheduled(
            round_fn_for, engine.make_state(w0), cfg.num_rounds,
            record=lambda r, a, s, e: {"round": r})
        assert np.asarray(state["w"]).tobytes() == np.asarray(w_ref).tobytes(), \\
            (strat, tau)
print("OK")
"""


class TestStrategyAxis:
    def test_distributed_round_programs_bitwise(self):
        # gather/bucketed/chunked shard_map programs, tau in {1, 4}: the
        # engine-driven loop must not perturb the collective numerics
        assert "OK" in run_sub(STRATEGY_PROG)


# ---------------------------------------------------------------------------
# Crash/resume: kill at every round boundary, resume bit-for-bit
# ---------------------------------------------------------------------------


class TestCrashResume:
    def test_scan_resume_every_round(self, tmp_path):
        # eager scan driver with an adaptive attack + error feedback —
        # every piece of cross-round state must round-trip
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=4,
                                num_rounds=8, compression="topk")
        atk = ATTACKS["alie"]
        ck = str(tmp_path / "lu")
        w_full, m_full = local_update_gd(linreg_loss, W0, SHARDS, cfg, atk,
                                         TRAJ, ckpt_every=1, ckpt_dir=ck)
        rounds = engine.snapshot_rounds(ck)
        assert rounds == list(range(1, cfg.num_rounds))
        for r in rounds:
            w_r, m_r = local_update_gd(linreg_loss, W0, SHARDS, cfg, atk,
                                       TRAJ, ckpt_every=1, ckpt_dir=ck,
                                       resume=r)
            assert_bitequal(w_r, w_full, f"resume@{r}")
            # rounds r..R replay exactly (the full-run tail)
            assert_bitequal(m_r, m_full[r:], f"metrics resume@{r}")
        # resume=True picks the latest snapshot
        w_t, _ = local_update_gd(linreg_loss, W0, SHARDS, cfg, atk, TRAJ,
                                 ckpt_dir=ck, resume=True)
        assert_bitequal(w_t, w_full, "resume=True")

    def test_scan_resume_fresh_dir_is_fresh_start(self, tmp_path):
        # --resume on an empty directory must run from scratch (CLI
        # idempotency on first launch)
        cfg = LocalUpdateConfig(method="median", step_size=0.05, num_rounds=4)
        w_plain, _ = local_update_gd(linreg_loss, W0, SHARDS, cfg)
        w_res, _ = local_update_gd(linreg_loss, W0, SHARDS, cfg,
                                   ckpt_every=2, ckpt_dir=str(tmp_path / "f"),
                                   resume=True)
        assert_bitequal(w_res, w_plain)

    def test_jit_regime_segmentation_invisible(self, tmp_path):
        # the donated-buffer jitted runner: full run == segmented run
        # with snapshots, bit-for-bit (the jit regime's resume contract)
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=2,
                                num_rounds=8, compression="topk")
        stages = make_local_update_stages(linreg_loss, SHARDS, cfg,
                                          ATTACKS["stale"], TRAJ)
        m = jax.tree.leaves(SHARDS)[0].shape[0]
        res0 = _init_comp_state(cfg.compression, W0, m)
        s_full, m_full = engine.run_scan(
            stages, engine.make_state(W0, comp_res=res0), cfg.num_rounds,
            jit=True)
        ck = str(tmp_path / "jit")
        s_seg, m_seg = engine.run_scan(
            stages, engine.make_state(W0, comp_res=res0), cfg.num_rounds,
            jit=True, ckpt_every=3, ckpt_dir=ck)
        assert_bitequal(s_seg["w"], s_full["w"])
        assert_bitequal(s_seg["comp_res"], s_full["comp_res"])
        assert_bitequal(m_seg, m_full)
        # and a resume from the mid-run snapshot lands on the same state
        s_res, _ = engine.run_scan(
            stages, engine.make_state(W0, comp_res=res0), cfg.num_rounds,
            jit=True, ckpt_every=3, ckpt_dir=ck, resume=6)
        assert_bitequal(s_res["w"], s_full["w"])
        assert_bitequal(s_res["comp_res"], s_full["comp_res"])

    def test_scheduled_resume_preserves_greedy_adversary(self, tmp_path):
        # killing the scheduled driver mid-run must restore the greedy
        # damage table: picks after resume match the uninterrupted run
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=2,
                                num_rounds=10, compression="int8")
        mixture = AttackMixture(
            (AttackConfig("sign_flip", alpha=0.25, scale=8.0),
             AttackConfig("alie", alpha=0.25),
             AttackConfig("stale", alpha=0.25)),
            schedule="greedy")
        ck = str(tmp_path / "sched")
        w_full, h_full = run_local_update_rounds(
            linreg_loss, W0, SHARDS, cfg, mixture, TRAJ,
            ckpt_every=1, ckpt_dir=ck)
        for r in engine.snapshot_rounds(ck):
            w_r, h_r = run_local_update_rounds(
                linreg_loss, W0, SHARDS, cfg, mixture, TRAJ,
                ckpt_every=1, ckpt_dir=ck, resume=r)
            assert_bitequal(w_r, w_full, f"resume@{r}")
            assert h_r == h_full, f"history resume@{r}"

    def test_fed_sync_resume_every_round(self, tmp_path, population):
        rcfg = RoundConfig(num_rounds=8, cohort_size=32, chunk_clients=8,
                           method="median", compression="topk", lr=0.3,
                           seed=3)
        mixture = AttackMixture(
            (AttackConfig("alie", alpha=0.25),
             AttackConfig("sign_flip", alpha=0.25, scale=8.0)),
            schedule="greedy")
        ck = str(tmp_path / "fed")
        w_full, h_full = run_rounds(population, rcfg, mixture,
                                    ckpt_every=1, ckpt_dir=ck)
        for r in engine.snapshot_rounds(ck):
            w_r, h_r = run_rounds(population, rcfg, mixture,
                                  ckpt_every=1, ckpt_dir=ck, resume=r)
            assert_bitequal(w_r, w_full, f"resume@{r}")
            assert h_r == h_full, f"history resume@{r}"

    def test_async_buffer_resume(self, tmp_path, population):
        # the async engine's full state: pending queue, staleness
        # histories, arrival scheduler, greedy attack scheduler
        rcfg = RoundConfig(num_rounds=8, cohort_size=32, chunk_clients=8,
                           method="median", lr=0.3, seed=3)
        acfg = AsyncConfig(buffer_k=16, max_staleness=3, policy="damped")
        arr = ArrivalConfig(latency="lognormal", scale=1.0, spread=1.0,
                            client_spread=0.5, dropout=0.05, churn=0.1)
        mixture = AttackMixture(
            (AttackConfig("sign_flip", alpha=0.25, scale=8.0),
             AttackConfig("stale_exploit", alpha=0.25)),
            schedule="greedy")
        ck = str(tmp_path / "async")
        w_full, h_full = run_async_rounds(population, rcfg, acfg, arr,
                                          mixture, ckpt_every=2, ckpt_dir=ck)
        rounds = engine.snapshot_rounds(ck)
        assert rounds, "async run wrote no snapshots"
        for r in rounds:
            w_r, h_r = run_async_rounds(population, rcfg, acfg, arr, mixture,
                                        ckpt_every=2, ckpt_dir=ck, resume=r)
            assert_bitequal(w_r, w_full, f"resume@{r}")
            assert h_r == h_full, f"history resume@{r}"

    def test_robust_gd_resume(self, tmp_path):
        cfg = RobustGDConfig(method="trimmed_mean", beta=0.3, step_size=0.1,
                             num_iters=7)
        atk = ATTACKS["stale"]
        ck = str(tmp_path / "rgd")
        w_full, m_full = robust_gd(linreg_loss, W0, SHARDS, cfg, atk, TRAJ,
                                   ckpt_every=2, ckpt_dir=ck)
        for r in engine.snapshot_rounds(ck):
            w_r, _ = robust_gd(linreg_loss, W0, SHARDS, cfg, atk, TRAJ,
                               ckpt_every=2, ckpt_dir=ck, resume=r)
            assert_bitequal(w_r, w_full, f"resume@{r}")
        assert_bitequal(w_full, legacy_robust_gd(
            linreg_loss, W0, SHARDS, cfg, atk, TRAJ)[0],
            "segmented run vs legacy single scan")
