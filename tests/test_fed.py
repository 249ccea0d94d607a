"""Federated-scale subsystem: histogram-sketch aggregation within one bin
width of the exact estimators, streaming chunk invariance, population
determinism, the round loop's Byzantine robustness, and the distributed
``chunked`` strategy."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg
from repro.core.attacks import AttackConfig
from repro.fed import streaming
from repro.fed.population import ClientPopulation, PopulationConfig
from repro.fed.rounds import AttackMixture, RoundConfig, aggregate_cohort, run_rounds
from repro.kernels import histogram_agg as H

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sketch(x, nbins: int):
    """(counts, sums, lo, width) of the full-array histogram, f32 jnp."""
    return H.sketch_array(jnp.asarray(x), nbins)


class TestHistogramWithinOneBin:
    """Acceptance criterion: |sketch − exact| ≤ bin width on every input."""

    # even and odd m; d=133 is not a multiple of the 128-lane block
    MS = [6, 7, 64, 101]
    DS = [5, 133]

    @pytest.mark.fast
    @pytest.mark.parametrize("m", MS)
    @pytest.mark.parametrize("d", DS)
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_median_random(self, m, d, dtype):
        rng = np.random.default_rng(m * 100 + d)
        x = jnp.asarray(rng.standard_normal((m, d)) * 3, dtype=dtype)
        nbins = 64
        counts, _, lo, width = _sketch(x, nbins)
        got = np.asarray(H.median_from_hist(counts, lo, width, m))
        exact = np.median(np.asarray(x, np.float32), axis=0)
        w = np.asarray(width)
        assert (np.abs(got - exact) <= w * 1.0001 + 1e-6).all()

    @pytest.mark.parametrize("m", MS)
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_trimmed_mean_random(self, m, dtype):
        rng = np.random.default_rng(m)
        x = jnp.asarray(rng.standard_normal((m, 77)) * 2, dtype=dtype)
        nbins, beta = 64, 0.1
        counts, sums, lo, width = _sketch(x, nbins)
        got = np.asarray(H.trimmed_mean_from_hist(counts, sums, lo, width, m, beta))
        xf = np.asarray(x, np.float32)
        b = int(beta * m)
        exact = np.sort(xf, axis=0)[b : m - b].mean(0)
        assert (np.abs(got - exact) <= np.asarray(width) * 1.0001 + 1e-5).all()

    def test_adversarial_rows(self):
        """Byzantine rows at ±huge values stretch the bin range; the sketch
        median must still land within one (now wide) bin of the exact
        median, and stay inside the honest envelope for sane bin counts."""
        rng = np.random.default_rng(3)
        m, q, d = 25, 10, 40
        honest = rng.standard_normal((m - q, d)).astype(np.float32)
        adv = np.full((q, d), 1e4, np.float32)
        x = np.concatenate([adv, honest])
        nbins = 65536  # wide range / many bins -> sub-honest-scale width
        counts, _, lo, width = _sketch(x, nbins)
        got = np.asarray(H.median_from_hist(counts, lo, width, m))
        exact = np.median(x, axis=0)
        assert (np.abs(got - exact) <= np.asarray(width) + 1e-6).all()

    @pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 1.0])
    def test_quantile_random(self, q):
        """quantile_from_hist tracks the nearest-rank coordinate_quantile
        within one bin width."""
        rng = np.random.default_rng(int(q * 100))
        m = 41
        x = jnp.asarray(rng.standard_normal((m, 50)) * 2, jnp.float32)
        counts, _, lo, width = _sketch(x, 64)
        got = np.asarray(H.quantile_from_hist(counts, lo, width, m, q))
        exact = np.asarray(agg.coordinate_quantile(x, q))
        assert (np.abs(got - exact) <= np.asarray(width) * 1.0001 + 1e-6).all()

    def test_degenerate_constant_coordinate(self):
        x = np.full((12, 4), 1.75, np.float32)
        counts, sums, lo, width = _sketch(x, 32)
        assert np.allclose(np.asarray(H.median_from_hist(counts, lo, width, 12)), 1.75)
        assert np.allclose(
            np.asarray(H.trimmed_mean_from_hist(counts, sums, lo, width, 12, 0.25)), 1.75)

    @pytest.mark.fast
    def test_registered_in_get_aggregator(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal((33, 3, 5)), jnp.float32)
        for name in ("approx_median", "approx_trimmed_mean"):
            out = agg.get_aggregator(name, beta=0.1)(x)
            assert out.shape == (3, 5)
        flat = np.asarray(x).reshape(33, -1)
        w = (flat.max(0) - flat.min(0)) / 256
        got = np.asarray(agg.get_aggregator("approx_median")(x)).reshape(-1)
        assert (np.abs(got - np.median(flat, 0)) <= w + 1e-6).all()


class TestPallasKernels:
    def test_minmax_matches_jnp(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((19, 300)), jnp.float32)
        lo, hi = H.minmax_pallas(x, block=128, interpret=True)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(x).min(0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(hi), np.asarray(x).max(0), rtol=1e-6)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_histogram_kernel_matches_scatter_path(self, dtype):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((33, 261)), dtype=dtype)  # 261 % 128 != 0
        lo, hi = H.minmax_pallas(x, block=128, interpret=True)
        nbins = 32
        width = (hi - lo) / nbins
        cp, sp = H.histogram_pallas(x, lo, width, nbins=nbins, block=128, interpret=True)
        cj, sj = H.hist_update(*H.hist_init(261, nbins), x, lo, width)
        np.testing.assert_allclose(np.asarray(cp), np.asarray(cj))
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sj), atol=1e-3)

    def test_streaming_pallas_backend_matches_xla(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((40, 130)), jnp.float32)
        outs = {}
        for backend in ("xla", "pallas"):
            cfg = streaming.SketchConfig(nbins=64, backend=backend, block=128)
            outs[backend] = np.asarray(
                streaming.aggregate_array_chunked(x, "median", chunk_rows=16, cfg=cfg))
        np.testing.assert_allclose(outs["xla"], outs["pallas"], rtol=1e-6, atol=1e-6)


class TestStreaming:
    @pytest.mark.fast
    @pytest.mark.parametrize("chunk_rows", [7, 16, 1000])
    def test_chunk_invariance(self, chunk_rows):
        """Streaming over chunks (uneven tail included) must equal the
        single-shot sketch — the estimator is a function of the histogram
        alone, however it was accumulated."""
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.standard_normal((100, 23)), jnp.float32)
        cfg = streaming.SketchConfig(nbins=64, backend="xla")
        whole = np.asarray(streaming.aggregate_array_chunked(x, "median", chunk_rows=1000, cfg=cfg))
        chunked = np.asarray(streaming.aggregate_array_chunked(x, "median", chunk_rows=chunk_rows, cfg=cfg))
        np.testing.assert_allclose(whole, chunked, rtol=1e-6, atol=1e-6)

    def test_streaming_trimmed_mean_and_mean(self):
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.standard_normal((60, 11)), jnp.float32)
        cfg = streaming.SketchConfig(nbins=128, backend="xla")
        tm = np.asarray(streaming.aggregate_array_chunked(x, "trimmed_mean", 0.1, 17, cfg))
        xf = np.asarray(x)
        exact = np.sort(xf, 0)[6:54].mean(0)
        w = (xf.max(0) - xf.min(0)) / 128
        assert (np.abs(tm - exact) <= w + 1e-6).all()
        mean = np.asarray(streaming.aggregate_array_chunked(x, "mean", chunk_rows=13, cfg=cfg))
        np.testing.assert_allclose(mean, xf.mean(0), rtol=1e-5, atol=1e-6)


class TestPopulation:
    def test_deterministic_and_lazy(self):
        pop = ClientPopulation(PopulationConfig(num_clients=10_000, dim=8, seed=1))
        ids = jnp.asarray([0, 17, 9999], jnp.int32)
        w = jnp.zeros(8)
        g1 = np.asarray(pop.client_grads(w, ids))
        g2 = np.asarray(pop.client_grads(w, ids))
        np.testing.assert_array_equal(g1, g2)  # regenerable => two-pass safe
        # different clients draw different shards
        assert not np.allclose(g1[0], g1[1])

    def test_cohort_sampling_without_replacement(self):
        pop = ClientPopulation(PopulationConfig(num_clients=500, dim=4))
        ids = np.asarray(pop.sample_cohort(jax.random.PRNGKey(0), 200))
        assert len(np.unique(ids)) == 200
        assert ids.min() >= 0 and ids.max() < 500

    def test_byzantine_subpopulation(self):
        pop = ClientPopulation(PopulationConfig(num_clients=1000, alpha=0.1, dim=4))
        assert pop.cfg.num_byzantine() == 100
        mask = np.asarray(pop.is_byzantine(jnp.arange(1000, dtype=jnp.int32)))
        assert mask.sum() == 100 and mask[:100].all()

    def test_heterogeneity_shifts_optima(self):
        iid = ClientPopulation(PopulationConfig(num_clients=100, dim=16, noise=0.0, seed=2))
        het = ClientPopulation(PopulationConfig(num_clients=100, dim=16, noise=0.0,
                                                heterogeneity=1.0, seed=2))
        ids = jnp.arange(64, dtype=jnp.int32)
        # at w = w*, iid clients (no noise) have ~zero gradients; heterogeneous don't
        g_iid = np.asarray(iid.client_grads(iid.w_star, ids))
        g_het = np.asarray(het.client_grads(het.w_star, ids))
        assert np.abs(g_iid).max() < 1e-5
        assert np.linalg.norm(g_het, axis=1).mean() > 0.1


class TestRounds:
    def _pop(self, alpha):
        return ClientPopulation(PopulationConfig(
            num_clients=2000, samples_per_client=32, dim=16, alpha=alpha, seed=0))

    def _run(self, method, attack_name, alpha=0.1, rounds=8, **atk_kw):
        pop = self._pop(alpha)
        rcfg = RoundConfig(num_rounds=rounds, cohort_size=256, chunk_clients=64,
                           method=method, nbins=256, backend="xla", lr=0.2, seed=0)
        mix = AttackMixture((AttackConfig(attack_name, alpha=alpha, **atk_kw),)) \
            if attack_name else AttackMixture()
        _, hist = run_rounds(pop, rcfg, mix)
        return hist

    def test_sign_flip_median_converges_mean_diverges(self):
        med = self._run("approx_median", "sign_flip", scale=100.0)
        mean = self._run("stream_mean", "sign_flip", scale=100.0)
        assert med[-1]["err"] < med[0]["err"] and med[-1]["err"] < 0.5, med[-1]
        assert mean[-1]["err"] > 10 * med[-1]["err"], (mean[-1], med[-1])

    def test_alie_trimmed_mean_converges(self):
        tm = self._run("approx_trimmed_mean", "alie", shift=1.0)
        assert tm[-1]["err"] < tm[0]["err"] and tm[-1]["err"] < 0.5, tm[-1]

    def test_attack_mixture_cycles(self):
        mix = AttackMixture((AttackConfig("sign_flip", alpha=0.1),
                             AttackConfig("alie", alpha=0.1)))
        assert mix.for_round(0).name == "sign_flip"
        assert mix.for_round(1).name == "alie"
        assert mix.for_round(2).name == "sign_flip"
        assert AttackMixture().for_round(5) is None

    def test_streaming_matches_exact_within_bin_width(self):
        """approx_median cohort aggregate vs the exact median of the fully
        materialized cohort gradients — same chunks, same attack."""
        pop = self._pop(0.1)
        w = jnp.zeros(16)
        ids = pop.sample_cohort(jax.random.PRNGKey(1), 256)
        atk = AttackConfig("sign_flip", alpha=0.1, scale=10.0)
        ap = RoundConfig(cohort_size=256, chunk_clients=64, method="approx_median",
                         nbins=512, backend="xla")
        ex = RoundConfig(cohort_size=256, chunk_clients=64, method="median")
        got = np.asarray(aggregate_cohort(pop, w, ids, ap, atk))
        exact = np.asarray(aggregate_cohort(pop, w, ids, ex, atk))
        # reconstruct bin width from the attacked cohort matrix
        from repro.fed.rounds import _chunk_bounds, _make_chunk_fn
        bounds = _chunk_bounds(256, 64)
        fn = _make_chunk_fn(pop, w, ids, bounds, atk)
        full = np.concatenate([np.asarray(fn(j)) for j in range(len(bounds))])
        width = (full.max(0) - full.min(0)) / 512
        assert (np.abs(got - exact) <= width * 1.0001 + 1e-6).all()


class TestDeterminism:
    """Same seed ⇒ identical cohort, arrival order, and final iterate —
    regardless of chunk size, on BOTH engines (the ISSUE's determinism
    regression).  Chunk-size pins use chunk-size-invariant attacks
    (none / stale_exploit, whose payloads read only the broadcast
    history); stats-oracle attacks are chunk-local by design."""

    def _pop(self):
        return ClientPopulation(PopulationConfig(
            num_clients=400, samples_per_client=16, dim=8, alpha=0.1,
            noise=0.5, seed=0))

    def _rcfg(self, chunk):
        return RoundConfig(num_rounds=4, cohort_size=32, chunk_clients=chunk,
                           method="median", lr=0.3, seed=0)

    @pytest.mark.parametrize("attack", [None, "stale_exploit"])
    def test_sync_chunk_size_invariant(self, attack):
        pop = self._pop()
        mix = AttackMixture((AttackConfig(attack, alpha=0.1),)) \
            if attack else AttackMixture()
        w8, h8 = run_rounds(pop, self._rcfg(8), mix)
        w32, h32 = run_rounds(pop, self._rcfg(32), mix)
        np.testing.assert_array_equal(np.asarray(w8), np.asarray(w32))
        assert [h["err"] for h in h8] == [h["err"] for h in h32]

    @pytest.mark.parametrize("attack", [None, "stale_exploit"])
    def test_async_chunk_size_invariant(self, attack):
        from repro.fed.async_rounds import AsyncConfig, run_async_rounds
        from repro.fed.population import ArrivalConfig

        pop = self._pop()
        mix = AttackMixture((AttackConfig(attack, alpha=0.1),)) \
            if attack else AttackMixture()
        acfg = AsyncConfig(buffer_k=16, policy="damped")
        arr = ArrivalConfig(latency="lognormal", dropout=0.1, churn=0.1)
        w8, h8 = run_async_rounds(pop, self._rcfg(8), acfg, arr, mix)
        w32, h32 = run_async_rounds(pop, self._rcfg(32), acfg, arr, mix)
        np.testing.assert_array_equal(np.asarray(w8), np.asarray(w32))
        # arrival order / buffer composition pinned too, not just the iterate
        for a, b in zip(h8, h32):
            assert a["duration"] == b["duration"]
            assert a["buffer"] == b["buffer"]
            assert a["staleness_mean"] == b["staleness_mean"]
            assert a["pending"] == b["pending"]

    def test_async_rerun_identical(self):
        from repro.fed.async_rounds import AsyncConfig, run_async_rounds
        from repro.fed.population import ArrivalConfig

        pop = self._pop()
        mix = AttackMixture((AttackConfig("stale_exploit", alpha=0.1),))
        acfg = AsyncConfig(buffer_k=12, policy="trim_late")
        arr = ArrivalConfig(latency="exponential", dropout=0.2,
                            client_spread=0.5)
        w1, h1 = run_async_rounds(pop, self._rcfg(16), acfg, arr, mix)
        w2, h2 = run_async_rounds(pop, self._rcfg(16), acfg, arr, mix)
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        assert h1 == h2


@pytest.mark.slow
def test_large_cohort_smoke_100k():
    """A 10⁵-client cohort streams through the sketch in 512-row chunks;
    peak live state is (512, d) gradients + (nbins, d) sketch — the
    (10⁵, d) matrix is never built. Checked against the exact median of
    the same rows (accumulated chunk-wise for the oracle only)."""
    pop = ClientPopulation(PopulationConfig(
        num_clients=100_000, samples_per_client=4, dim=8, seed=3))
    rcfg = RoundConfig(cohort_size=100_000, chunk_clients=512,
                       method="approx_median", nbins=256, backend="xla")
    w = jnp.zeros(8)
    ids = pop.sample_cohort(jax.random.PRNGKey(0), 100_000)
    got = np.asarray(aggregate_cohort(pop, w, ids, rcfg))
    from repro.fed.rounds import _chunk_bounds, _make_chunk_fn
    bounds = _chunk_bounds(100_000, 512)
    fn = _make_chunk_fn(pop, w, ids, bounds, None)
    full = np.concatenate([np.asarray(fn(j)) for j in range(len(bounds))])
    width = (full.max(0) - full.min(0)) / 256
    assert (np.abs(got - np.median(full, 0)) <= width * 1.0001 + 1e-6).all()


def test_cli_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.fed.run", "--clients", "500", "--cohort", "64",
         "--chunk", "32", "--rounds", "2", "--dim", "8", "--alpha", "0.1",
         "--attack", "sign_flip", "--method", "approx_median", "--backend", "xla"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final |w-w*|" in r.stdout


def test_distributed_chunked_strategy():
    """psum-based chunked strategy inside shard_map: sketch median within
    one bin width of the global exact median; Byzantine simulation matches
    the apply_gradient_attack oracle. Runs in a subprocess with a forced
    8-device CPU platform (same harness as test_distributed.py)."""
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed
    from repro.core.attacks import AttackConfig, apply_gradient_attack
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    g_all = np.random.default_rng(0).standard_normal((8, 37)).astype(np.float32)

    def mk(method, attack=None):
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P(),
                           axis_names={"data"}, check_vma=False)
        def f(g):
            return distributed.robust_chunked_agg(
                {"w": g[0]}, ("data",), method, beta=0.25, attack=attack,
                nbins=256, coord_chunk=16)["w"]
        return f

    width = (g_all.max(0) - g_all.min(0)) / 256
    out = np.asarray(mk("median")(jnp.asarray(g_all)))
    assert (np.abs(out - np.median(g_all, 0)) <= width + 1e-6).all()
    tm = np.asarray(mk("trimmed_mean")(jnp.asarray(g_all)))
    want = np.sort(g_all, 0)[2:6].mean(0)
    assert (np.abs(tm - want) <= width + 1e-6).all()
    np.testing.assert_allclose(np.asarray(mk("mean")(jnp.asarray(g_all))),
                               g_all.mean(0), rtol=1e-5)
    # approx_median (the configs/CLI name) is an alias of median here
    np.testing.assert_allclose(np.asarray(mk("approx_median")(jnp.asarray(g_all))),
                               out, rtol=1e-6)
    atk = AttackConfig("alie", alpha=0.25, shift=1.5)
    out_atk = np.asarray(mk("median", attack=atk)(jnp.asarray(g_all)))
    oracle = np.asarray(apply_gradient_attack(atk, jnp.asarray(g_all), atk.byzantine_mask(8)))
    w_atk = (oracle.max(0) - oracle.min(0)) / 256
    assert (np.abs(out_atk - np.median(oracle, 0)) <= w_atk + 1e-5).all()
    print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"


class TestStreamingMulti:
    """streaming_aggregate_multi: several estimators from ONE shared
    two-pass sketch (the streaming analogue of the fused selection
    kernel)."""

    def test_matches_single_method_calls(self):
        from repro.fed import streaming

        cfg = streaming.SketchConfig(nbins=256, backend="xla")
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((120, 19)), jnp.float32)
        bounds = [(s, min(s + 32, 120)) for s in range(0, 120, 32)]
        chunk_fn = lambda j: x[bounds[j][0]:bounds[j][1]]  # noqa: E731
        multi = streaming.streaming_aggregate_multi(
            chunk_fn, len(bounds), 19, ("mean", "median", "trimmed_mean"), 0.1, cfg)
        for method in ("mean", "median", "trimmed_mean"):
            single = streaming.streaming_aggregate(
                chunk_fn, len(bounds), 19, method, 0.1, cfg)
            np.testing.assert_allclose(np.asarray(multi[method]),
                                       np.asarray(single), rtol=1e-6, atol=1e-6)

    def test_accuracy_and_unknown_method(self):
        from repro.fed import streaming

        cfg = streaming.SketchConfig(nbins=512, backend="xla")
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((200, 23)), jnp.float32)
        out = streaming.aggregate_array_chunked(x, "median", chunk_rows=64, cfg=cfg)
        xa = np.asarray(x)
        width = (xa.max(0) - xa.min(0)) / 512
        assert (np.abs(np.asarray(out) - np.median(xa, 0)) <= width + 1e-6).all()
        with pytest.raises(ValueError):
            streaming.streaming_aggregate_multi(
                lambda j: x, 1, 23, ("median", "geometric_median"), cfg=cfg)
