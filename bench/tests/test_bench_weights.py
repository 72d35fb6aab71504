"""The prepared-weight cache: a hit loads, a changed key misses; and the
weight bytes the rooflines count are the prepared operands' sizes."""
import dataclasses
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, weights, work
from bench.harness import _weight_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.json")


def _fake_root(tmp_path):
    root = tmp_path / "root"
    (root / "src" / "repro").mkdir(parents=True)
    (root / "src" / "repro" / "a.py").write_text("x = 1\n")
    cfg = root / "tiny.json"
    shutil.copy(TINY, cfg)
    return str(root), str(cfg)


def test_source_key_covers_config_and_program(tmp_path):
    root, cfg = _fake_root(tmp_path)
    m = model.load(cfg)
    k0 = weights.source_key(m, cfg, root)
    assert weights.source_key(m, cfg, root) == k0
    with open(os.path.join(root, "src", "repro", "a.py"), "a") as f:
        f.write("y = 2\n")
    k1 = weights.source_key(m, cfg, root)
    assert k1 != k0
    with open(cfg, "a") as f:
        f.write("\n")
    k2 = weights.source_key(m, cfg, root)
    assert k2 not in (k0, k1)
    # the architecture's module: its leaves, shapes and tree
    arch = tmp_path / "arch.py"
    shutil.copy(m.arch.__file__, arch)
    m2 = dataclasses.replace(m, arch=types.SimpleNamespace(
        __file__=str(arch)))
    k3 = weights.source_key(m2, cfg, root)
    assert weights.source_key(m2, cfg, root) == k3
    with open(arch, "a") as f:
        f.write("# the tree changed\n")
    assert weights.source_key(m2, cfg, root) not in (k0, k1, k2, k3)


def test_hit_loads_and_changed_key_misses(tmp_path, monkeypatch):
    root, cfg = _fake_root(tmp_path)
    m = model.load(cfg)
    builds = []

    def fake_build(m, log=print):
        builds.append(1)
        return {"w": jnp.arange(4.0) + len(builds)}, dict(
            draw=0.0, quantize=0.0, prepare=0.0)

    monkeypatch.setattr(weights, "build", fake_build)
    cache = str(tmp_path / "cache")
    t1, b1 = weights.load_or_build(m, cfg, cache, root=root)
    t2, b2 = weights.load_or_build(m, cfg, cache, root=root)
    assert b1 is not None and b2 is None and len(builds) == 1
    np.testing.assert_array_equal(np.asarray(t1["w"]), np.asarray(t2["w"]))
    with open(os.path.join(root, "src", "repro", "a.py"), "a") as f:
        f.write("# the quantizer changed\n")
    t3, b3 = weights.load_or_build(m, cfg, cache, root=root)
    assert b3 is not None and len(builds) == 2
    assert len(os.listdir(cache)) == 1           # the stale entry went


def test_leaf_draws_are_seeded_and_independent():
    m = model.load(TINY)
    a = weights.leaf(m, "wq", 1)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(weights.leaf(m, "wq", 1)))
    assert not np.array_equal(np.asarray(a),
                              np.asarray(weights.leaf(m, "wq", 0)))
    assert a.shape == (m.d_model, m.arch.q_dim(m)) and a.dtype == jnp.float32


@pytest.mark.parametrize("bits", [2, 3])
def test_kernel_bytes_are_the_prepared_operands(bits):
    """At a small shape the fused matmul's byte count is the prepared
    weight's stored tensors plus the activations, and the dequant
    kernel's adds the dense float32 output."""
    from repro.launch.quantize import quantize_tree
    from repro.launch.steps import prepare_serving_params
    import importlib.util

    def mod(name):
        path = os.path.join(HERE, os.pardir, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("lm_" + name, path)
        out = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(out)
        return out

    d_in, d_out, L = 256, 384, 2
    w = jax.random.normal(jax.random.PRNGKey(0), (L, d_in, d_out)) * 0.05
    q, _ = quantize_tree({"wq": w}, bits, gamma=0.05)
    p = prepare_serving_params(q, mode="prepared", fmt="v2")["wq"]
    stored = sum(int(t.nbytes) for t in (p.codes, p.syms, p.offs, p.dbase,
                                         p.codebooks))
    assert stored == round(p.bits_per_weight() * L * d_in * d_out / 8)
    m = model.load(TINY)
    params = {"stack": {"attn": {n: p for n in m.arch.ATTN},
                        "mlp": {n: p for n in m.arch.MLP}},
              "lm_head": p}
    wb = _weight_bytes(model.Model(**{**m.__dict__, "n_layers": L}), params)
    assert wb.layer_bytes["wq"] * L == stored
    assert wb.lm_head_bytes == stored
    M = 8
    flops, nbytes = mod("icq_matmul_v2_roofline").call_work(
        M, d_in, d_out, stored // L, 2)
    assert flops == 2 * M * d_in * d_out
    assert nbytes == stored // L + M * (d_in + d_out) * 2
    _, dq = mod("icq_dequant_v2_roofline").call_work(d_in, d_out,
                                                     stored // L)
    assert dq == stored // L + d_in * d_out * 4


def test_step_work_counts():
    m = model.load(TINY)
    a = m.arch
    wb = work.Weights({n: 100 for n, _, _ in a.layer_linears(m)}, 50)
    s = work.Step("decode", [(10, 1), (0, 1)], 0, 2)
    assert a.step_flops(m, s) == (
        2 * a.linear_params(m) * 2 + 2 * m.d_model * m.vocab * 2
        + a.attn_flops(m, 10, 1) + a.attn_flops(m, 0, 1))
    assert a.attn_flops(m, 10, 1) == 4 * m.n_layers * m.block.n_heads \
        * m.block.head_dim * 11
    assert a.step_bytes(m, wb, s) == (
        m.n_layers * 700 + 50 + (11 + 1) * a.kv_row_bytes(m)
        + 2 * m.d_model * 2)
    pk = {"flops": 1e12, "hbm_bytes": 1e9}
    assert work.least_s(m, wb, s, pk) == max(
        a.step_flops(m, s) / 1e12, a.step_bytes(m, wb, s) / 1e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite") == {"flops": 197e12, "hbm_bytes": 819e9}
