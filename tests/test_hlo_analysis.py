"""The trip-count-aware HLO analyzer vs known-cost programs — including the
demonstration that XLA's cost_analysis counts while bodies once."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis, roofline


def _scan_model(L, n=128):
    w = jnp.zeros((L, n, n))

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None

        x, _ = jax.lax.scan(body, x, w)
        return x.sum()

    return jax.jit(f).lower(w, jnp.ones((4, n))).compile()


def test_plain_matmul_flops_exact():
    c = jax.jit(lambda x, w: x @ w).lower(jnp.ones((8, 64)), jnp.ones((64, 32))).compile()
    r = hlo_analysis.analyze(c.as_text())
    assert r["flops"] == 2 * 8 * 64 * 32


def test_xla_cost_analysis_ignores_trip_count():
    """The bug this module exists to fix."""
    f2 = _scan_model(2).cost_analysis()["flops"]
    f8 = _scan_model(8).cost_analysis()["flops"]
    assert f2 == f8  # XLA: body counted once


def test_scan_flops_scale_with_layers():
    for L in (2, 8, 126):
        r = hlo_analysis.analyze(_scan_model(L).as_text())
        assert r["flops"] == pytest.approx(2 * 4 * 128 * 128 * L, rel=1e-6), L


def test_grad_scan_counts_recompute():
    L, n = 8, 64

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, w)
        return (x ** 2).sum()

    c = jax.jit(jax.grad(f)).lower(jnp.zeros((L, n, n)), jnp.ones((4, n))).compile()
    r = hlo_analysis.analyze(c.as_text())
    # fwd + recompute + dgrad + wgrad = 4 matmuls/layer
    assert r["flops"] == pytest.approx(4 * 2 * 4 * n * n * L, rel=0.05)


def test_scan_bytes_not_billed_full_buffer():
    """Scans must bill the per-iteration weight slice, not the full stack."""
    L, n = 64, 128
    r = hlo_analysis.analyze(_scan_model(L, n).as_text())
    per_iter = r["bytes"] / L
    slice_bytes = n * n * 4
    assert per_iter < 8 * slice_bytes  # would be ~L× slice_bytes if mis-billed


def test_collective_bytes_with_trip_count():
    import functools
    import subprocess, sys, os, textwrap
    # needs multiple devices -> subprocess
    code = textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import hlo_analysis
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), axis_names={"data"}, check_vma=False)
        def f(x):
            def body(c, xl):
                g = jax.lax.all_gather(xl, "data", tiled=True)
                return c + g.sum(), None
            out, _ = jax.lax.scan(body, 0.0, x[0])
            return out.reshape(1)
        c = jax.jit(f).lower(jnp.ones((8, 4, 128))).compile()
        r = hlo_analysis.analyze(c.as_text())
        assert r["collective_bytes"] == 4 * 8 * 128 * 4, r
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]


def test_roofline_terms():
    t = roofline.roofline_terms(flops=197e12, hbm_bytes=0, coll_bytes=0)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["dominant"] == "compute"
    t = roofline.roofline_terms(flops=0, hbm_bytes=819e9, coll_bytes=0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["dominant"] == "memory"
    t = roofline.roofline_terms(flops=0, hbm_bytes=0, coll_bytes=4 * 50e9)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["dominant"] == "collective"


def test_model_flops():
    assert roofline.model_flops(1e9, 1000, "train") == 6e12
    assert roofline.model_flops(1e9, 1000, "decode") == 2e12
