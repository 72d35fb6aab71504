"""A sparse-expert architecture added as a module alone: leaves that cover
only some layers (a leading dense layer's MLP, the expert layers' router
and experts) and carry an expert axis, drawn, built one layer of one leaf
at a time, quantized by the reference per 2-D slice, counted per launch
from the program's own counters, and served end to end through the
program's ``moe`` family (``data/sparse_expert_arch.py``, written to an
architecture directory of its own)."""
import dataclasses
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model, reference, weights, work

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = os.path.join(HERE, "data", "sparse_expert_arch.py")
CONFIG = os.path.join(HERE, "data", "sparse_expert.json")
TRAFFIC = os.path.join(HERE, "data", "tiny_traffic.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("root")
    (root / "bench" / "archs").mkdir(parents=True)
    shutil.copy(ARCH, root / "bench" / "archs")
    return str(root)


@pytest.fixture(scope="module")
def m(root):
    return model.load(CONFIG, root)


@pytest.fixture(scope="module")
def built(m):
    """The tree ``build`` gives, every float32 draw it made, and its log."""
    draws, logs = [], []

    def noted(f):
        def g(*a):
            out = f(*a)
            draws.append((out.shape, out.dtype))
            return out
        return g

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(weights, "_draw_jit", noted(weights._draw_jit))
        mp.setattr(weights, "_draw_experts_jit",
                   noted(weights._draw_experts_jit))
        tree, _ = weights.build(m, log=logs.append)
    return tree, draws, logs


def test_expert_std_is_one_over_sqrt_d_in(m):
    w = np.asarray(weights.leaf(m, "expert_gate", 1))
    E, d_in, _ = w.shape
    assert E == 4 and d_in == m.d_model
    np.testing.assert_allclose(w.reshape(E, -1).std(-1), d_in ** -0.5,
                               rtol=0.1)
    assert weights._std("expert_gate", (64, 2048, 1408)) == 1 / np.sqrt(2048)
    assert weights._std("wq", (2048, 1024)) == 1 / np.sqrt(2048)
    assert weights._std("bias", (1024,)) == 1 / np.sqrt(1024)


def test_experts_and_layers_draw_independently(m):
    a = np.asarray(weights.leaf(m, "expert_up", 1)).reshape(4, -1)
    b = np.asarray(weights.leaf(m, "expert_up", 2)).reshape(4, -1)
    c = np.corrcoef(np.concatenate([a, b]))
    off = c[~np.eye(8, dtype=bool)]
    assert np.abs(off).max() < 0.1


def test_a_share_of_experts_draws_the_whole_models_numbers(m):
    share = dataclasses.replace(m, block=dataclasses.replace(
        m.block, experts=range(1, 3)))
    for name in ("expert_gate", "expert_down"):
        whole = np.asarray(weights.leaf(m, name, 2))
        np.testing.assert_array_equal(
            np.asarray(weights.leaf(share, name, 2)), whole[1:3])
        np.testing.assert_array_equal(
            np.asarray(reference.quantized(share, name, 2)),
            np.asarray(reference.quantized(m, name, 2))[1:3])


def test_a_leaf_exists_only_in_its_layers(m, built):
    tree, _, _ = built
    assert list(weights.layers(m, "w_gate")) == [0]
    assert list(weights.layers(m, "expert_gate")) == [1, 2]
    assert list(weights.layers(m, "wq")) == [0, 1, 2]
    assert weights.layers(m, "lm_head") is None
    r = weights.stacked(m, "router")
    np.testing.assert_array_equal(
        np.asarray(r), np.stack([weights.leaf(m, "router", li)
                                 for li in (1, 2)]))
    dense, sparse = tree["dense_stack"], tree["stack"]
    assert dense["mlp"]["w_gate"].codes.shape[0] == 1
    assert "moe" not in dense and "mlp" not in sparse
    assert sparse["moe"]["w_gate"].codes.shape[:2] == (2, 4)
    assert dense["attn"]["wq"].codes.shape[0] == 1
    assert sparse["attn"]["wq"].codes.shape[0] == 2
    assert dense["ln1"].shape == (1, m.d_model)
    # the router is left unquantized by the program, and served as drawn
    np.testing.assert_array_equal(sparse["moe"]["router"], np.asarray(r))
    assert sparse["moe"]["router"].dtype == np.float32


def test_build_holds_one_layer_of_one_leaf_in_float32(m, built):
    _, draws, logs = built
    shapes = {tuple(m.arch.leaf_shape(m, n)) for n, _ in m.arch.LEAVES}
    layers = [weights.layers(m, n) for n, _ in m.arch.LEAVES]
    assert len(draws) == sum(1 if ls is None else len(ls) for ls in layers)
    assert all(s in shapes and d == jnp.float32 for s, d in draws)
    # no bits counted for the router
    quantized = sum(
        np.prod(m.arch.leaf_shape(m, n)) * len(weights.layers(m, n) or [0])
        for n, _ in m.arch.LEAVES
        if n not in m.arch.NORMS + ("embed", "router"))
    assert f"over {quantized / 1e6:.1f}M quantized weights" in logs[-1]


def test_reference_quantizes_an_expert_stack_slice_by_slice(m):
    w = weights.leaf(m, "expert_down", 2)
    want = np.stack([np.asarray(reference.icq_rtn(s, m.bits, m.gamma))
                     for s in w])
    np.testing.assert_array_equal(
        np.asarray(reference.quantized(m, "expert_down", 2)), want)


@pytest.fixture(scope="module")
def served(root, tmp_path_factory):
    """One run end to end, and the window's launches as the harness saw
    them."""
    drivers = []

    class Noted(harness.Driver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            drivers.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Driver", Noted)
        r = harness.run(CONFIG, TRAFFIC, {"output_tok_s": "tokens/s",
                                          "setup_s": "s"}, {},
                        seed=2147483907, seconds=1.0, trace=False,
                        require_tpu=False,
                        cache_dir=str(tmp_path_factory.mktemp("weights")),
                        root=root)
    return r, drivers[0]


def test_sparse_expert_model_runs_end_to_end_correct(served):
    r, drv = served
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert drv.steps_ok
    gap = r["check"]["logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_step_counters_reach_step_bytes(m, served):
    _, drv = served
    assert drv.steps
    for s in drv.steps:
        live = s.counters["fused_rows_live"]
        assert (live > 0) == (s.kind == "fused")
    wb = work.Weights({n: 1000 for n in m.arch._mats(m) if n != "router"},
                      10)
    pk = work.peaks("TPU v5 lite")
    for s in drv.steps:
        work.least_s(m, wb, s, pk)
    fused = next(s for s in drv.steps if s.kind == "fused")
    one, many = (dataclasses.replace(fused, counters={"fused_rows_live": n})
                 for n in (1, 8))
    assert m.arch.step_bytes(m, wb, one) < m.arch.step_bytes(m, wb, many)
