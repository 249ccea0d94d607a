"""repro.rounds — communication-round subsystem tests.

Pins the subsystem's four load-bearing contracts:

- Theorem 7: the quadratic one-round estimator's error obeys the
  Õ(α/√n + 1/√(nm) + 1/n) rate (core.theory.one_round_rate) across an
  (α, n, m) grid, and the streaming-histogram path agrees with the
  exact vmap reference within sketch tolerance;
- τ=1 local-update GD is **bit-for-bit** core.robust_gd.robust_gd (same
  vmap layout, attack keys, aggregate carry), and one round at large τ
  equals the one-round estimator (the interpolation endpoints);
- the distributed round programs fire exactly ONE robust aggregation
  per round regardless of τ (collective counts in the traced jaxpr are
  τ-independent; the launch/steps train step is HLO-asserted the same
  way on jax with the public shard_map API);
- attack-engine round integration: per-round greedy scheduling advances
  (explore → exploit), adaptive attacks see the previous aggregate, and
  omniscient attacks are rejected at BUILD time on stats-only
  strategies.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import theory
from repro.core.attacks import AttackConfig
from repro.core.robust_gd import (
    RobustGDConfig,
    linreg_loss,
    make_worker_shards,
    robust_gd,
)
from repro.fed.rounds import AttackMixture
from repro.launch.mesh import make_mesh
from repro.rounds import (
    CommBudget,
    LocalUpdateConfig,
    OneRoundConfig,
    comm,
    local_update_gd,
    make_gd_local_solver,
    one_round,
    one_round_streaming,
    quadratic_local_solver,
    run_local_update_rounds,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _linreg(alpha_unused, n, m, d=16, sigma=0.5, seed=0):
    kx, kn, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    N = n * m
    x = jax.random.normal(kx, (N, d))
    w_star = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    y = x @ w_star + sigma * jax.random.normal(kn, (N,))
    return make_worker_shards((x, y), m), w_star


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# CommBudget + strategy registry
# ---------------------------------------------------------------------------


class TestCommAccounting:
    def test_byte_formulas(self):
        d, m, B = 1000, 16, 4
        per = {s: comm.get_strategy_spec(s).bytes_per_round(d, m, B)
               for s in comm.registered_strategies()}
        assert per["gather"] == m * d * B
        assert per["bucketed"] == 2 * d * B
        assert per["rs"] == d * B
        assert per["chunked"] == (2 + 2 * 256) * d * B

    @pytest.mark.fast
    def test_chunked_bytes_independent_of_m(self):
        spec = comm.get_strategy_spec("chunked")
        assert spec.bytes_per_round(1000, 8, 4) == spec.bytes_per_round(1000, 10**5, 4)
        # ... unlike gather, which grows linearly
        g = comm.get_strategy_spec("gather")
        assert g.bytes_per_round(1000, 10**5, 4) == 12500 * g.bytes_per_round(1000, 8, 4)

    def test_budget_accumulates(self):
        b = CommBudget(strategy="bucketed", num_params=100, m=8)
        b.charge(10)
        b.charge()
        assert b.rounds == 11
        assert b.total_bytes == 11 * b.bytes_per_round
        rep = b.report()
        assert rep["bytes_formula"] == comm.get_strategy_spec("bucketed").bytes_formula
        with pytest.raises(ValueError):
            b.charge(-1)

    def test_registry_covers_docs_and_dispatch(self):
        names = set(comm.registered_strategies())
        # every ParallelConfig.agg_strategy value + the fsdp backward path
        assert {"gather", "bucketed", "chunked", "psum", "hierarchical", "rs"} == names

    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError, match="unknown strategy"):
            comm.get_strategy_spec("nope")

    @pytest.mark.fast
    def test_omniscient_rejected_on_stats_only_strategy(self):
        with pytest.raises(ValueError, match="omniscient"):
            comm.validate_attack_strategy(AttackConfig("mimic", alpha=0.1), "chunked")
        with pytest.raises(ValueError, match="omniscient"):
            comm.validate_attack_strategy(AttackConfig("max_damage_tm", alpha=0.1),
                                          "chunked")
        # everything up to stats is fine on chunked; omniscient ok on gather
        comm.validate_attack_strategy(AttackConfig("alie", alpha=0.1), "chunked")
        comm.validate_attack_strategy(AttackConfig("label_flip", alpha=0.1), "chunked")
        comm.validate_attack_strategy(AttackConfig("mimic", alpha=0.1), "gather")
        comm.validate_attack_strategy(None, "chunked")
        comm.validate_attack_strategy(AttackConfig("none"), "chunked")

    def test_resolve_attack_forms(self):
        spec, alpha, strength = comm.resolve_attack(
            AttackConfig("sign_flip", alpha=0.25, scale=7.0))
        assert spec.name == "sign_flip" and alpha == 0.25 and strength == 7.0
        spec, alpha, strength = comm.resolve_attack("alie")
        assert spec.name == "alie" and alpha is None
        assert comm.resolve_attack(None) == (None, None, None)
        assert comm.resolve_attack("none") == (None, None, None)
        assert comm.resolve_attack(AttackConfig("none")) == (None, None, None)


# ---------------------------------------------------------------------------
# one-round algorithm: Theorem 7 rate + execution-path agreement
# ---------------------------------------------------------------------------

K_ONE_ROUND = 2.5  # universal-constant calibration (worst seed-0 ratio ~1.25)


class TestOneRoundTheorem7:
    def test_rate_bound_over_grid(self):
        """err <= K·σ·√d·(α/√n + 1/√(nm) + 1/n) across the (α, n, m) grid
        — the Theorem 7 rate check against core/theory.py."""
        d, sigma = 16, 0.5
        for alpha in (0.0, 0.1, 0.2):
            for m in (8, 32):
                for n in (32, 128):
                    shards, w_star = _linreg(alpha, n, m, d, sigma)
                    atk = (AttackConfig("sign_flip", alpha=alpha, scale=10.0)
                           if alpha else None)
                    w = one_round(quadratic_local_solver, shards,
                                  OneRoundConfig("median"), attack=atk)
                    err = float(jnp.linalg.norm(w - w_star))
                    bound = K_ONE_ROUND * sigma * np.sqrt(d) * \
                        theory.one_round_rate(alpha, n, m)
                    assert err <= bound, (alpha, n, m, err, bound)

    def test_error_improves_with_n(self):
        """The 1/√(nm) term: quadrupling per-worker n must cut the clean
        error (well beyond seed noise)."""
        errs = {}
        for n in (32, 512):
            shards, w_star = _linreg(0.0, n, 16)
            w = one_round(quadratic_local_solver, shards, OneRoundConfig("median"))
            errs[n] = float(jnp.linalg.norm(w - w_star))
        assert errs[512] < 0.6 * errs[32], errs

    def test_median_survives_where_mean_breaks(self):
        shards, w_star = _linreg(0.2, 64, 16)
        atk = AttackConfig("sign_flip", alpha=0.2, scale=50.0)
        w_med = one_round(quadratic_local_solver, shards,
                          OneRoundConfig("median"), attack=atk)
        w_mean = one_round(quadratic_local_solver, shards,
                           OneRoundConfig("mean"), attack=atk)
        assert float(jnp.linalg.norm(w_med - w_star)) < 0.5
        assert float(jnp.linalg.norm(w_mean - w_star)) > 5.0

    @pytest.mark.fast
    def test_streaming_matches_vmap_reference(self):
        shards, _ = _linreg(0.0, 32, 64)
        cfg = OneRoundConfig("median")
        w_ref = one_round(quadratic_local_solver, shards, cfg)
        w_str = one_round_streaming(quadratic_local_solver, shards, cfg,
                                    chunk_workers=16, nbins=512)
        # sketch tolerance: one bin width per coordinate
        assert float(jnp.max(jnp.abs(w_ref - w_str))) < 5e-3

    def test_streaming_under_attack_matches_chunked_convention(self):
        """Byzantine rows replaced per chunk (ids below the cut), stats
        attacks using chunk-local honest statistics — median still lands
        near the clean estimate.  (Attack scale moderate on purpose: the
        equal-width sketch's bin width grows with the attacked value
        range — the documented sketch limitation, not under test here.)"""
        shards, w_star = _linreg(0.25, 64, 64)
        atk = AttackConfig("large_value", alpha=0.25, scale=50.0)
        w_med = one_round_streaming(quadratic_local_solver, shards,
                                    OneRoundConfig("median"), attack=atk,
                                    chunk_workers=16, nbins=512)
        w_mean = one_round_streaming(quadratic_local_solver, shards,
                                     OneRoundConfig("mean"), attack=atk,
                                     chunk_workers=16, nbins=512)
        assert float(jnp.linalg.norm(w_med - w_star)) < 1.0
        assert float(jnp.linalg.norm(w_mean - w_star)) > 2.0

    def test_adaptive_attacks_rejected(self):
        """One round has no previous aggregate: a prev-agg-reading attack
        would silently degrade to the zero attack, so it must raise."""
        shards, _ = _linreg(0.0, 16, 4, d=4)
        with pytest.raises(ValueError, match="adaptive"):
            one_round(quadratic_local_solver, shards, OneRoundConfig("median"),
                      attack=AttackConfig("stale", alpha=0.25))
        with pytest.raises(ValueError, match="adaptive"):
            one_round_streaming(quadratic_local_solver, shards,
                                OneRoundConfig("median"),
                                attack=AttackConfig("stale", alpha=0.25))
        from repro.rounds import one_round_distributed

        mesh = make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="adaptive"):
            one_round_distributed(quadratic_local_solver, shards, mesh,
                                  OneRoundConfig("median"),
                                  attack=AttackConfig("stale", alpha=0.25))

    def test_legacy_core_wrapper_still_exports(self):
        from repro.core import one_round as legacy

        assert legacy.one_round is one_round
        assert legacy.OneRoundConfig is OneRoundConfig
        assert legacy.quadratic_local_solver is quadratic_local_solver
        assert legacy.make_gd_local_solver is make_gd_local_solver


# ---------------------------------------------------------------------------
# local-update GD: the tau interpolation
# ---------------------------------------------------------------------------


class TestLocalUpdateInterpolation:
    @pytest.mark.fast
    def test_tau1_bit_for_bit_robust_gd(self):
        """τ=1 ≡ Algorithm 1, exactly: same final iterate and metrics to
        the bit, clean and under static/randomized/adaptive attacks."""
        shards, w_star = _linreg(0.25, 64, 16, d=8)
        w0 = jnp.zeros((8,))
        traj = lambda w: jnp.linalg.norm(w - w_star)  # noqa: E731
        for atk in (None,
                    AttackConfig("alie", alpha=0.25, shift=1.5),
                    AttackConfig("gauss", alpha=0.25),
                    AttackConfig("stale", alpha=0.25)):
            for method in ("median", "trimmed_mean"):
                g_cfg = RobustGDConfig(method=method, beta=0.3, step_size=0.1,
                                       num_iters=25)
                l_cfg = LocalUpdateConfig(method=method, beta=0.3, step_size=0.1,
                                          tau=1, num_rounds=25)
                wg, mg = robust_gd(linreg_loss, w0, shards, g_cfg, atk, traj)
                wl, ml = local_update_gd(linreg_loss, w0, shards, l_cfg, atk, traj)
                assert np.array_equal(np.asarray(wg), np.asarray(wl)), \
                    (atk and atk.name, method)
                assert np.array_equal(np.asarray(mg), np.asarray(ml))

    def test_one_round_of_large_tau_is_the_one_round_estimator(self):
        """τ→∞ endpoint: one communication round of τ local steps equals
        aggregating the τ-step local solutions (Algorithm 2), because
        coordinate-wise aggregators are translation-equivariant."""
        shards, _ = _linreg(0.0, 64, 16, d=8)
        w0 = jnp.zeros((8,))
        cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=60,
                                num_rounds=1)
        wl, _ = local_update_gd(linreg_loss, w0, shards, cfg)
        solver = make_gd_local_solver(linreg_loss, w0, steps=60, lr=0.05)
        wo = one_round(solver, shards, OneRoundConfig("median"))
        np.testing.assert_allclose(np.asarray(wl), np.asarray(wo),
                                   rtol=1e-5, atol=1e-6)

    def test_larger_tau_fewer_rounds_same_error(self):
        """The communication-efficiency claim at reference scale: τ=8
        reaches τ=1's 48-round error in 6 rounds (same local-step budget,
        8× fewer aggregations)."""
        shards, w_star = _linreg(0.1, 64, 16, d=8)
        w0 = jnp.zeros((8,))
        traj = lambda w: jnp.linalg.norm(w - w_star)  # noqa: E731
        atk = AttackConfig("alie", alpha=0.1, shift=1.5)
        base = LocalUpdateConfig(method="median", step_size=0.05, tau=1,
                                 num_rounds=48)
        few = LocalUpdateConfig(method="median", step_size=0.05, tau=8,
                                num_rounds=6)
        _, errs1 = local_update_gd(linreg_loss, w0, shards, base, atk, traj)
        _, errs8 = local_update_gd(linreg_loss, w0, shards, few, atk, traj)
        assert float(errs8[-1]) <= 1.15 * float(errs1[-1]), \
            (float(errs8[-1]), float(errs1[-1]))

    def test_tau_must_be_positive(self):
        shards, _ = _linreg(0.0, 16, 4, d=4)
        with pytest.raises(ValueError, match="tau"):
            local_update_gd(linreg_loss, jnp.zeros((4,)), shards,
                            LocalUpdateConfig(tau=0, num_rounds=1))

    def test_bare_attack_name_needs_alpha(self):
        """A non-None attack without a Byzantine fraction must raise —
        everywhere in the subsystem — rather than silently run clean
        while the caller believes the run was attacked."""
        shards, _ = _linreg(0.0, 16, 4, d=4)
        with pytest.raises(ValueError, match="Byzantine fraction"):
            local_update_gd(linreg_loss, jnp.zeros((4,)), shards,
                            LocalUpdateConfig(num_rounds=1), attack="alie")
        with pytest.raises(ValueError, match="Byzantine fraction"):
            one_round(quadratic_local_solver, shards, OneRoundConfig("median"),
                      attack="alie")
        with pytest.raises(ValueError, match="Byzantine fraction"):
            one_round_streaming(quadratic_local_solver, shards,
                                OneRoundConfig("median"), attack="sign_flip")


class TestScheduledRounds:
    def _setup(self):
        shards, w_star = _linreg(0.25, 64, 16, d=8)
        traj = lambda w: jnp.linalg.norm(w - w_star)  # noqa: E731
        return shards, jnp.zeros((8,)), traj

    def test_greedy_schedule_advances_per_round(self):
        """Round-level adaptive adversary: explore each candidate once,
        then replay the most damaging (sign_flip dominates zero)."""
        shards, w0, traj = self._setup()
        mix = AttackMixture((AttackConfig("zero", alpha=0.25),
                             AttackConfig("sign_flip", alpha=0.25, scale=20.0)),
                            schedule="greedy")
        cfg = LocalUpdateConfig(method="median", step_size=0.1, tau=4,
                                num_rounds=8)
        _, hist = run_local_update_rounds(linreg_loss, w0, shards, cfg, mix, traj)
        names = [h["attack"] for h in hist]
        assert names[:2] == ["zero", "sign_flip"]  # exploration sweep
        assert all(n == "sign_flip" for n in names[2:]), names  # exploitation
        assert all(h["tau"] == 4 for h in hist)

    def test_cycle_schedule_and_history(self):
        shards, w0, traj = self._setup()
        mix = AttackMixture((AttackConfig("zero", alpha=0.25),
                             AttackConfig("gauss", alpha=0.25)),
                            schedule="cycle")
        cfg = LocalUpdateConfig(method="median", step_size=0.1, tau=2,
                                num_rounds=4)
        w, hist = run_local_update_rounds(linreg_loss, w0, shards, cfg, mix, traj)
        assert [h["attack"] for h in hist] == ["zero", "gauss", "zero", "gauss"]
        assert hist[-1]["metric"] == pytest.approx(float(traj(w)))

    def test_adaptive_attack_sees_previous_aggregate(self):
        """The stale attack replays the prior round's broadcast aggregate:
        its round-2+ payload must differ from the zero attack's (round 1
        they coincide — prev_agg starts at zero)."""
        shards, w0, traj = self._setup()
        cfg = LocalUpdateConfig(method="mean", step_size=0.1, tau=2, num_rounds=5)
        _, h_stale = run_local_update_rounds(
            linreg_loss, w0, shards, cfg,
            AttackMixture((AttackConfig("stale", alpha=0.25),), schedule="fixed"),
            traj)
        _, h_zero = run_local_update_rounds(
            linreg_loss, w0, shards, cfg,
            AttackMixture((AttackConfig("zero", alpha=0.25),), schedule="fixed"),
            traj)
        assert h_stale[0]["metric"] == pytest.approx(h_zero[0]["metric"])
        assert abs(h_stale[-1]["metric"] - h_zero[-1]["metric"]) > 1e-5

    def test_clean_rounds_converge(self):
        shards, w0, traj = self._setup()
        cfg = LocalUpdateConfig(method="median", step_size=0.1, tau=4,
                                num_rounds=12)
        _, hist = run_local_update_rounds(linreg_loss, w0, shards, cfg, None, traj)
        assert hist[-1]["metric"] < 0.25 * hist[0]["metric"]


# ---------------------------------------------------------------------------
# distributed round programs (subprocess: multi-device CPU mesh)
# ---------------------------------------------------------------------------

PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.attacks import AttackConfig
from repro.core.robust_gd import make_worker_shards, linreg_loss
from repro.launch.mesh import make_mesh
from repro.rounds import (LocalUpdateConfig, OneRoundConfig,
                          make_local_update_round, local_update_gd,
                          one_round, one_round_distributed,
                          quadratic_local_solver)

mesh = make_mesh((8,), ("data",))
kx, kn, kw = jax.random.split(jax.random.PRNGKey(0), 3)
d, n, m = 6, 32, 8
x = jax.random.normal(kx, (n*m, d))
w_star = jax.random.normal(kw, (d,))/jnp.sqrt(d)
y = x @ w_star + 0.3*jax.random.normal(kn, (n*m,))
shards = make_worker_shards((x, y), m)
w0 = jnp.zeros((d,))
"""


class TestDistributedRounds:
    def test_one_round_distributed_matches_reference(self):
        run_sub(PRELUDE + """
w_ref = one_round(quadratic_local_solver, shards, OneRoundConfig("median"))
for strat, tol in (("gather", 1e-6), ("bucketed", 1e-6), ("chunked", 2e-3)):
    w = one_round_distributed(quadratic_local_solver, shards, mesh,
                              OneRoundConfig("median"), strategy=strat)
    assert float(jnp.max(jnp.abs(w - w_ref))) < tol, strat
print("OK")
""")

    def test_one_round_distributed_under_attack(self):
        run_sub(PRELUDE + """
atk = AttackConfig("sign_flip", alpha=0.25, scale=10.0)
w = one_round_distributed(quadratic_local_solver, shards, mesh,
                          OneRoundConfig("median"), strategy="bucketed",
                          attack=atk)
assert float(jnp.linalg.norm(w - w_star)) < 0.5
w_mean = one_round_distributed(quadratic_local_solver, shards, mesh,
                               OneRoundConfig("mean"), strategy="bucketed",
                               attack=atk)
assert float(jnp.linalg.norm(w_mean - w_star)) > 1.0
print("OK")
""")

    def test_local_update_round_matches_single_host(self):
        run_sub(PRELUDE + """
cfg = LocalUpdateConfig(method="median", step_size=0.05, tau=4, num_rounds=6)
step = make_local_update_round(linreg_loss, cfg, mesh, strategy="bucketed")
w = w0
for r in range(cfg.num_rounds):
    w = step(w, shards, jnp.int32(r))
w_ref, _ = local_update_gd(linreg_loss, w0, shards, cfg)
np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6, atol=1e-7)
print("OK")
""")

    def test_one_collective_per_round_any_tau(self):
        """THE structural claim: scanning tau local steps must not scale
        the collective count — jaxpr collective eqns identical for tau=1
        and tau=16 on every strategy."""
        run_sub(PRELUDE + """
def counts(tau, strategy):
    c = LocalUpdateConfig(method="median", step_size=0.05, tau=tau, num_rounds=1)
    f = make_local_update_round(linreg_loss, c, mesh, strategy=strategy)
    txt = str(jax.make_jaxpr(lambda w, data, r: f(w, data, r))(w0, shards, jnp.int32(0)))
    return {k: txt.count(k + "[") for k in ("all_gather", "all_to_all", "psum")}

for strategy in ("gather", "bucketed", "chunked"):
    c1, c16 = counts(1, strategy), counts(16, strategy)
    assert c1 == c16, (strategy, c1, c16)
    assert sum(c16.values()) >= 1, (strategy, c16)
print("OK")
""")

    def test_build_time_attack_validation(self):
        # no devices needed: validation fires before any tracing
        from repro.rounds import make_local_update_round, one_round_distributed

        shards, _ = _linreg(0.0, 16, 4, d=4)
        mesh = make_mesh((1,), ("data",))
        cfg = LocalUpdateConfig(num_rounds=1)
        with pytest.raises(ValueError, match="omniscient"):
            one_round_distributed(quadratic_local_solver, shards, mesh,
                                  OneRoundConfig("median"), strategy="chunked",
                                  attack=AttackConfig("mimic", alpha=0.25))
        with pytest.raises(ValueError, match="omniscient"):
            make_local_update_round(linreg_loss, cfg, mesh, strategy="chunked",
                                    attack=AttackConfig("max_damage_tm", alpha=0.25))
        with pytest.raises(ValueError, match="adaptive"):
            make_local_update_round(linreg_loss, cfg, mesh, strategy="gather",
                                    attack=AttackConfig("stale", alpha=0.25))


# ---------------------------------------------------------------------------
# launch/steps integration (public shard_map API — newer jax legs of CI)
# ---------------------------------------------------------------------------


def test_train_step_one_collective_per_round_hlo():
    """local_steps=4 scans the local updates INSIDE the train step: the
    lowered StableHLO must contain a while loop and exactly the same
    number of collectives as local_steps=1 (the aggregation fires once
    per round, not per local step)."""
    run_sub("""
import re
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, ParallelConfig
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("llama3.2-3b")
mesh = make_debug_mesh(4, 2)
opt = get_optimizer("sgd", 1e-2)
shape_t = ShapeConfig("t", 64, 8, "train")

def lowered_text(local_steps):
    pcfg = ParallelConfig(agg_method="median", agg_strategy="gather",
                          remat=False, attn_chunk=0, local_steps=local_steps)
    with jax.set_mesh(mesh):
        params = steps.abstract_params(cfg, mesh)
        state = steps.abstract_opt_state(opt, cfg, mesh)
        ins = steps.input_specs(cfg, shape_t, mesh)
        fn = steps.make_train_step(cfg, pcfg, mesh, opt, None)
        return fn.lower(params, state, ins, jnp.int32(0)).as_text()

def coll_counts(txt):
    return {k: len(re.findall(k, txt))
            for k in ("all_gather", "all_to_all", "all_reduce",
                      "reduce_scatter", "collective_permute")}

t1, t4 = lowered_text(1), lowered_text(4)
c1, c4 = coll_counts(t1), coll_counts(t4)
assert c1 == c4, (c1, c4)
assert sum(c4.values()) >= 1, c4
assert "while" in t4  # the tau-step scan
print("OK", c1)
""")


def test_train_step_local_rounds_still_learn():
    """local_steps=4 training on the debug mesh still reduces the loss
    (end-to-end: scan + single aggregation + optimizer rescale)."""
    run_sub("""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, ParallelConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.data.pipeline import DataConfig, make_lm_batch, host_to_mesh
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("llama3.2-3b")
mesh = make_debug_mesh(4, 2)
dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=32, global_batch=8, num_workers=4)
opt = get_optimizer("adamw", 2e-3)
pcfg = ParallelConfig(agg_method="median", agg_strategy="gather", remat=False,
                      attn_chunk=0, local_steps=4, local_lr=5e-3)
with jax.set_mesh(mesh):
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pshard = steps.param_shardings(cfg, mesh)
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
    state = opt.init(params)
    fn = steps.make_train_step(cfg, pcfg, mesh, opt, None)
    losses = []
    for i in range(6):
        batch = host_to_mesh(make_lm_batch(dcfg, i), mesh, ("data",))
        params, state, metrics = fn(params, state, batch, jnp.int32(i))
        losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0], losses
print("OK", losses[0], losses[-1])
""")


def test_train_step_rejects_local_steps_with_fsdp():
    from repro.configs import ParallelConfig
    from repro.configs import get_smoke_config
    from repro.launch import steps
    from repro.optim.optimizers import get_optimizer

    cfg = get_smoke_config("llama3.2-3b")
    mesh = make_mesh((1, 1), ("data", "model"))
    pcfg = ParallelConfig(param_mode="fsdp", local_steps=4)
    with pytest.raises(ValueError, match="local_steps"):
        steps.make_train_step(cfg, pcfg, mesh, get_optimizer("sgd", 1e-2))
    # invalid tau must raise, not silently clamp to aggregate-every-step
    with pytest.raises(ValueError, match="local_steps"):
        steps.make_train_step(cfg, ParallelConfig(local_steps=0), mesh,
                              get_optimizer("sgd", 1e-2))


# ---------------------------------------------------------------------------
# fed local-update cohort rounds
# ---------------------------------------------------------------------------


class TestFedLocalUpdateRounds:
    def _pop(self, alpha=0.0):
        from repro.fed.population import ClientPopulation, PopulationConfig

        return ClientPopulation(PopulationConfig(
            num_clients=256, samples_per_client=16, dim=16, alpha=alpha, seed=3))

    @pytest.mark.fast
    def test_client_deltas_tau1_equals_grads(self):
        pop = self._pop()
        w = jnp.ones((16,)) * 0.1
        ids = jnp.arange(32, dtype=jnp.int32)
        g = pop.client_grads(w, ids)
        d1 = pop.client_deltas(w, ids, 1, 0.1)
        # same math, different fusion (scan body vs straight-line): allclose
        np.testing.assert_allclose(np.asarray(g), np.asarray(d1),
                                   rtol=1e-5, atol=1e-7)

    def test_local_update_rounds_converge(self):
        from repro.fed.rounds import RoundConfig, run_rounds

        pop = self._pop()
        rcfg = RoundConfig(num_rounds=8, cohort_size=128, chunk_clients=64,
                           method="median", local_steps=4, local_lr=0.1, lr=0.4)
        _, hist = run_rounds(pop, rcfg)
        assert hist[-1]["err"] < 0.5 * hist[0]["err"], hist[-1]

    def test_adaptive_attack_sees_transmitted_scale_aggregate(self):
        """prev_agg handed to adaptive attacks must be the TRANSMITTED
        (Σ-of-τ-gradients) aggregate, not the 1/τ-rescaled optimizer
        input — pinned with an explicit two-round oracle for the stale
        attack under mean aggregation."""
        from repro.fed.rounds import AttackMixture, RoundConfig, run_rounds

        pop = self._pop(alpha=0.25)
        tau, lr_loc, cohort = 4, 0.1, 64
        rcfg = RoundConfig(num_rounds=2, cohort_size=cohort,
                           chunk_clients=cohort, method="mean",
                           local_steps=tau, local_lr=lr_loc,
                           optimizer="sgd", lr=0.4, seed=0)
        atk = AttackConfig("stale", alpha=0.25, strength=1.0)
        _, hist = run_rounds(pop, rcfg,
                             AttackMixture((atk,), schedule="fixed"))

        # oracle replay with pop primitives
        root = jax.random.PRNGKey(rcfg.seed)
        w = jnp.zeros((pop.cfg.dim,))
        ids0 = pop.sample_cohort(jax.random.fold_in(root, 0), cohort)
        d0 = pop.client_deltas(w, ids0, tau, lr_loc)
        byz0 = pop.is_byzantine(ids0)[:, None]
        g0 = jnp.mean(jnp.where(byz0, 0.0, d0), axis=0)  # stale r0: prev=0
        w1 = w - rcfg.lr * (g0 / tau)
        ids1 = pop.sample_cohort(jax.random.fold_in(root, 1), cohort)
        d1 = pop.client_deltas(w1, ids1, tau, lr_loc)
        byz1 = pop.is_byzantine(ids1)[:, None]
        # round 1: Byzantine rows replay the TRANSMITTED-scale g0; history
        # records the 1/τ-rescaled optimizer input of that aggregate
        g1 = jnp.mean(jnp.where(byz1, g0[None, :], d1), axis=0)
        assert hist[1]["grad_norm"] == pytest.approx(
            float(jnp.linalg.norm(g1)) / tau, rel=1e-4)
        # and NOT the rescaled-prev_agg variant (the bug this pins)
        g1_bug = jnp.mean(jnp.where(byz1, g0[None, :] / tau, d1), axis=0)
        assert hist[1]["grad_norm"] != pytest.approx(
            float(jnp.linalg.norm(g1_bug)) / tau, rel=1e-3)

    def test_local_update_rounds_robust_under_attack(self):
        from repro.fed.rounds import AttackMixture, RoundConfig, run_rounds

        pop = self._pop(alpha=0.2)
        mix = AttackMixture((AttackConfig("sign_flip", alpha=0.2, scale=20.0),),
                            schedule="fixed")
        base = dict(num_rounds=8, cohort_size=128, chunk_clients=64,
                    local_steps=4, local_lr=0.1, lr=0.4)
        _, h_med = run_rounds(pop, RoundConfig(method="median", **base), mix)
        _, h_mean = run_rounds(pop, RoundConfig(method="mean", **base), mix)
        assert h_med[-1]["err"] < h_mean[-1]["err"], (h_med[-1], h_mean[-1])


# ---------------------------------------------------------------------------
# comm-efficiency benchmark plumbing (fast sanity of the gating logic)
# ---------------------------------------------------------------------------


class TestCommBenchmark:
    def test_rounds_to_target(self):
        from benchmarks.comm_efficiency import _rounds_to

        assert _rounds_to([0.5, 0.2, 0.1], 0.2) == 2
        assert _rounds_to([0.5, 0.4], 0.1) is None

    def test_committed_grid_is_gated_and_clean(self):
        """BENCH_comm.json (the committed grid) must be theory-gated the
        same way as ROBUSTNESS.json: every record carries bound/gated/ok
        and none violates; the ALIE byte-saving gate holds."""
        import json

        path = os.path.join(ROOT, "BENCH_comm.json")
        assert os.path.exists(path), "committed BENCH_comm.json missing"
        with open(path) as f:
            payload = json.load(f)
        assert payload["suite"] == "comm"
        recs = payload["records"]
        assert len(recs) >= 36
        for r in recs:
            assert r["gated"] and "bound" in r and "err" in r
            assert r["ok"], r
        assert payload["violations"] == []
        alie = [g for g in payload["bytes_gates"] if g["attack"] == "alie"]
        tau = [g for g in alie if "bytes_saving_tau_ge_4" in g]
        int8 = [g for g in alie if "bytes_saving_int8_vs_none" in g]
        assert tau and all(g["ok"] and g["bytes_saving_tau_ge_4"] >= 4.0
                           for g in tau)
        assert int8 and all(g["ok"] and g["bytes_saving_int8_vs_none"] >= 3.0
                            for g in int8)
        # the codec axis is present and every codec appears in the grid
        comps = {r["compression"] for r in recs}
        assert comps >= {"none", "int8", "topk", "count_sketch"}
