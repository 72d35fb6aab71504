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


# sha256 (16 hex), dtype and shape of every array of the tiny prepared
# tree, computed with the whole-stack ``build`` of commit d4cb727 (each
# leaf drawn over all layers at once, then quantized and prepared), by
# its path: dict keys, then the index among a prepared weight's arrays
TINY_TREE = {
    'embed': ('448020170f54445b', 'bfloat16', (256, 64)),
    'final_norm': ('7649800c828dd357', 'bfloat16', (64,)),
    'lm_head/0': ('bf6275b748276033', 'uint32', (16, 256)),
    'lm_head/2': ('cf585c84deef7a55', 'float32', (8, 256)),
    'lm_head/3': ('f71d0508b40c0544', 'uint32', (1, 256)),
    'lm_head/4': ('d24f083bc4630b81', 'uint16', (2, 256)),
    'lm_head/5': ('5341e6b2646979a7', 'uint8', (1, 256)),
    'lm_head/6': ('70dd1063758e3ec0', 'uint32', (8, 256)),
    'stack/attn/wk/0': ('295d6f7ad0ee2ee5', 'uint32', (2, 16, 32)),
    'stack/attn/wk/2': ('f9af6ee13d5ecc9a', 'float32', (2, 8, 32)),
    'stack/attn/wk/3': ('b02cbf4eea4aa276', 'uint32', (2, 1, 32)),
    'stack/attn/wk/4': ('2d3a17319e0e9e54', 'uint16', (2, 2, 32)),
    'stack/attn/wk/5': ('f5a5fd42d16a2030', 'uint8', (2, 1, 32)),
    'stack/attn/wk/6': ('e83ae3480bf2006f', 'uint32', (2, 8, 32)),
    'stack/attn/wo/0': ('f2cb1ee7cc4a114a', 'uint32', (2, 16, 64)),
    'stack/attn/wo/2': ('a2b72529ca6e13f5', 'float32', (2, 8, 64)),
    'stack/attn/wo/3': ('df4067377d84f999', 'uint32', (2, 1, 64)),
    'stack/attn/wo/4': ('dab6e13104709e29', 'uint16', (2, 2, 64)),
    'stack/attn/wo/5': ('38723a2e5e8a17aa', 'uint8', (2, 1, 64)),
    'stack/attn/wo/6': ('0978ee4fdacead43', 'uint32', (2, 8, 64)),
    'stack/attn/wq/0': ('eae61b63ce4ecf6f', 'uint32', (2, 16, 64)),
    'stack/attn/wq/2': ('0735cb3a3c857b18', 'float32', (2, 8, 64)),
    'stack/attn/wq/3': ('ef4496114d1612ef', 'uint32', (2, 1, 64)),
    'stack/attn/wq/4': ('dab6e13104709e29', 'uint16', (2, 2, 64)),
    'stack/attn/wq/5': ('38723a2e5e8a17aa', 'uint8', (2, 1, 64)),
    'stack/attn/wq/6': ('c86e8d97af9c3e78', 'uint32', (2, 8, 64)),
    'stack/attn/wv/0': ('941bb5f153896636', 'uint32', (2, 16, 32)),
    'stack/attn/wv/2': ('fa9818794a8fd886', 'float32', (2, 8, 32)),
    'stack/attn/wv/3': ('30584a02a52b84e3', 'uint32', (2, 1, 32)),
    'stack/attn/wv/4': ('2d3a17319e0e9e54', 'uint16', (2, 2, 32)),
    'stack/attn/wv/5': ('f5a5fd42d16a2030', 'uint8', (2, 1, 32)),
    'stack/attn/wv/6': ('ed0b253180aaecfb', 'uint32', (2, 8, 32)),
    'stack/ln1': ('dc75f965aac6b745', 'bfloat16', (2, 64)),
    'stack/ln2': ('3e823738aff91f88', 'bfloat16', (2, 64)),
    'stack/mlp/w_down/0': ('e2bccc75604f7b8d', 'uint32', (2, 16, 64)),
    'stack/mlp/w_down/2': ('6cc6ecbf09f45dcd', 'float32', (2, 8, 64)),
    'stack/mlp/w_down/3': ('5087e59fb82fd2d3', 'uint32', (2, 2, 64)),
    'stack/mlp/w_down/4': ('3fbb53ef8de0ff09', 'uint16', (2, 2, 64)),
    'stack/mlp/w_down/5': ('38723a2e5e8a17aa', 'uint8', (2, 1, 64)),
    'stack/mlp/w_down/6': ('1ffced5d6ea1fe60', 'uint32', (2, 8, 64)),
    'stack/mlp/w_gate/0': ('dbc566de7a401735', 'uint32', (2, 16, 128)),
    'stack/mlp/w_gate/2': ('b9a571e80ce01e8c', 'float32', (2, 8, 128)),
    'stack/mlp/w_gate/3': ('0b579bd9ff829d31', 'uint32', (2, 1, 128)),
    'stack/mlp/w_gate/4': ('3c937d9db6c7f1f4', 'uint16', (2, 2, 128)),
    'stack/mlp/w_gate/5': ('5341e6b2646979a7', 'uint8', (2, 1, 128)),
    'stack/mlp/w_gate/6': ('07679347cec193ac', 'uint32', (2, 8, 128)),
    'stack/mlp/w_up/0': ('487480d9635c6593', 'uint32', (2, 16, 128)),
    'stack/mlp/w_up/2': ('1677351e95a7c9b4', 'float32', (2, 8, 128)),
    'stack/mlp/w_up/3': ('bc661f78d673dd10', 'uint32', (2, 1, 128)),
    'stack/mlp/w_up/4': ('3c937d9db6c7f1f4', 'uint16', (2, 2, 128)),
    'stack/mlp/w_up/5': ('5341e6b2646979a7', 'uint8', (2, 1, 128)),
    'stack/mlp/w_up/6': ('da18901d661625e6', 'uint32', (2, 8, 128)),
}
# and of the tree's structure, which holds each prepared weight's blocks
# and the checksums of its stored gap streams
TINY_TREEDEF = "c6377578488ad7d0"


def test_tiny_tree_built_layer_by_layer_is_the_parents():
    import hashlib
    import re

    def h(x):
        return hashlib.sha256(x).hexdigest()[:16]

    tree, _ = weights.build(model.load(TINY), log=lambda s: None)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    got = {}
    for path, a in flat:
        key = "/".join(a or b for a, b in re.findall(
            r"\['([^']+)'\]|\[<flat index (\d+)>\]",
            jax.tree_util.keystr(path)))
        a = np.ascontiguousarray(np.asarray(a))
        got[key] = (h(a.tobytes()), str(a.dtype), a.shape)
    assert got == TINY_TREE
    assert h(str(treedef).encode()) == TINY_TREEDEF


def test_layers_built_apart_equal_the_whole_stack_calls():
    """At a width where the layers' gap streams differ in length, the leaf
    built one layer at a time is what the program's quantize and prepare
    give for the whole stack at once, array for array and checksum for
    checksum."""
    from repro.launch.quantize import quantize_tree
    from repro.launch.steps import prepare_serving_params

    m = model.load(TINY)
    m = dataclasses.replace(m, n_layers=4, block=dataclasses.replace(
        m.block, d_ff=256))
    widths = {quantize_tree({"w_down": weights.leaf(m, "w_down", li)},
                            m.bits, gamma=m.gamma)[0]["w_down"]
              .symbols.shape[-1] for li in range(4)}
    assert len(widths) > 1
    got, n_w, _ = weights.built_leaf(m, "w_down", dict(
        draw=0.0, quantize=0.0, prepare=0.0))
    assert n_w == 4 * 256 * m.d_model
    q, _ = quantize_tree({"w_down": weights.stacked(m, "w_down")}, m.bits,
                         gamma=m.gamma, method=m.method)
    want = prepare_serving_params(q, mode="prepared", fmt=m.fmt)["w_down"]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
