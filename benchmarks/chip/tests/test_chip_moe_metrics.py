"""The grouped-matmul kernel's per-layer metrics (``moe_gmm_ms_per_step``,
``moe_gmm_roofline``) on a small trace with the kernel's op names, and a
hand count of ``moe_roofline.py``'s FLOPs and bytes at a tiny size."""
import types

import pytest

from chip_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import moe_roofline as M
from benchmarks.chip import peaks, run
from benchmarks.chip import trace as T

# the kernel's ops as the compiler names them (a v5e compile of the
# cell's window step, and of a grouped product's value and gradient)
KERNEL_OPS = ["moe_gmm.129", "jvp_moe_gmm_.11", "transpose_jvp_moe_gmm__.12",
              "moe_tgmm.42", "transpose_jvp_moe_tgmm__.11"]
OTHER_OPS = ["splash_mha_fwd_residuals.22", "fusion.556", "sort.3", "gather.17"]


def traced(ops):
    """A reduced trace of one window: the listed ops back to back, each
    1 ms, on one chip."""
    events = [(name, i * 1_000_000, (i + 1) * 1_000_000, "ops") for i, name in enumerate(ops)]
    trace = T.Trace(devices={"/device:TPU:0": events},
                    host=[(T.WINDOW, 0, len(ops) * 1_000_000, "main")])
    return T.reduce(trace)


def ctx_of(ops, steps=2):
    return types.SimpleNamespace(reduced=traced(ops), steps=steps, chips=1,
                                 peak=peaks.lookup("TPU v5 lite"))


@pytest.mark.parametrize("name", KERNEL_OPS)
def test_kernel_ops_match(name):
    assert M.KERNEL_OP.search(name)


@pytest.mark.parametrize("name", OTHER_OPS)
def test_other_ops_do_not(name):
    assert not M.KERNEL_OP.search(name)


def test_readers_on_a_small_trace():
    ctx = ctx_of(KERNEL_OPS + OTHER_OPS, steps=2)
    # five kernel ops of 1 ms over two steps
    assert run._reader("moe_gmm_ms_per_step")(ctx) == pytest.approx(2.5)
    cell = run.load_cell("train.granite-moe.m1")
    ideal = M.roofline_s(cell.config, cell.traffic, ctx.peak)
    assert run._reader("moe_gmm_roofline")(ctx) == pytest.approx(100 * ideal / 2.5e-3)


def test_readers_find_nothing_without_the_kernel():
    ctx = ctx_of(OTHER_OPS)
    assert run._reader("moe_gmm_ms_per_step")(ctx) is None
    assert run._reader("moe_gmm_roofline")(ctx) is None


def test_work_by_hand_at_a_tiny_size():
    # 2 layers, D 64, F 32, 8 experts, top 2; 4 rows of 32 tokens: R = 256
    c = {"hidden_size": 64, "intermediate_size": 32, "num_local_experts": 8,
         "num_experts_per_tok": 2, "num_hidden_layers": 2, "torch_dtype": "bfloat16"}
    tr = {"batch_per_worker": 4, "workers": 1, "seq_len": 32}
    flops, bytes_ = M.step_work(c, tr)
    # 24 T k D F per layer: 24 * 128 * 2 * 64 * 32 * 2 layers
    assert flops == 25_165_824
    # per product and call, bf16: 256 x 64 in, 256 x 32 out, 8 x 64 x 32
    # matrices = 81,920 bytes; 3 products, 4 calls, 2 layers
    assert bytes_ == 81_920 * 3 * 4 * 2
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert M.roofline_s(c, tr, peak) == pytest.approx(max(flops / 1e12, bytes_ / 1e9))


def test_cell_of_resolves_the_listed_cell():
    assert M.cell_of("moe_gmm_roofline").name == "train.granite-moe.m1"
