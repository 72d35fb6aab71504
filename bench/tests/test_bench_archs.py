"""A configuration finds its architecture's module by the name it
publishes: the real configurations resolve to the dense GQA module, an
unclaimed or twice-claimed name is an error that names it (and the
command exits 2 before any weights load), and a new module dropped into
the architecture directory is the one the harness then calls."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, manifest, model, work

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.json")
TINY_TRAFFIC = os.path.join(HERE, "data", "tiny_traffic.json")
DENSE = os.path.join(manifest.BENCH_DIR, "archs", "dense_gqa.py")


def _config(tmp_path, arch, name="tiny", src=TINY):
    with open(src) as f:
        d = json.load(f)
    d["name"] = name
    if arch is None:
        del d["published"]["architectures"]
    else:
        d["published"]["architectures"] = [arch]
    path = tmp_path / f"{name}.json"
    path.write_text(json.dumps(d))
    return str(path)


@pytest.mark.parametrize("cfg,arch", [
    ("internlm2-2b", "InternLM2ForCausalLM"),
    ("phi3-3b", "Phi3ForCausalLM"),
])
def test_real_configs_resolve_to_dense_gqa(cfg, arch):
    m = model.load(manifest.config_path(cfg, manifest.load()))
    assert os.path.samefile(m.arch.__file__, DENSE)
    assert arch in m.arch.ARCHITECTURES
    assert manifest.architecture(arch) is m.arch


@pytest.mark.parametrize("arch", ["NoSuchForCausalLM", None])
def test_unclaimed_architecture_fails_with_its_name(tmp_path, arch):
    cfg = _config(tmp_path, arch)
    with pytest.raises(manifest.ManifestError,
                       match=arch or "published.architectures"):
        model.load(cfg)


def test_architecture_claimed_twice_fails_with_its_name(tmp_path):
    archs = tmp_path / "bench" / "archs"
    archs.mkdir(parents=True)
    for n in ("a", "b"):
        (archs / f"{n}.py").write_text(
            'ARCHITECTURES = ("TwiceForCausalLM",)\n')
    with pytest.raises(manifest.ManifestError, match="TwiceForCausalLM"):
        manifest.architecture("TwiceForCausalLM", str(tmp_path))


def _tree(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".jax_cache",
                                                  ".trace", "probe",
                                                  "__pycache__"))
    return str(tmp_path)


def test_manifest_refuses_a_config_no_module_claims(tmp_path):
    root = _tree(tmp_path)
    m = manifest.load(root)
    path = manifest.config_path("phi3-3b", m, root)
    with open(path) as f:
        d = json.load(f)
    d["published"]["architectures"] = ["Phi4ForCausalLM"]
    with open(path, "w") as f:
        json.dump(d, f)
    with pytest.raises(manifest.ManifestError, match="Phi4ForCausalLM"):
        manifest.validate(m, root)


def test_command_exits_2_naming_an_unclaimed_architecture(tmp_path):
    """Before any weights load: the cell's own configuration names an
    architecture that no module claims."""
    root = _tree(tmp_path)
    path = manifest.config_path("internlm2-2b", manifest.load(root), root)
    with open(path) as f:
        d = json.load(f)
    d["published"]["architectures"] = ["DeepseekV2ForCausalLM"]
    with open(path, "w") as f:
        json.dump(d, f)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-2b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "DeepseekV2ForCausalLM" in p.stderr
    assert not os.path.exists(os.path.join(root, "bench", ".cache"))


# A test-only architecture: the dense block under another name, noting
# each of its functions the benchmark calls.
TOY = '''
from bench import manifest

_dense = manifest.architecture("InternLM2ForCausalLM")
globals().update({k: v for k, v in vars(_dense).items()
                  if not k.startswith("__")})
ARCHITECTURES = ("ToyDecoderForTest",)
LOG = @LOG@


def _noted(name):
    f = getattr(_dense, name)

    def g(*a, **k):
        with open(LOG, "a") as fh:
            fh.write(name + "\\n")
        return f(*a, **k)
    return g


for _n in ("block", "program_config", "tree", "leaf_shape", "layer_bytes",
           "layer_weights", "layer", "step_flops", "step_bytes",
           "matmul_calls", "dequant_calls", "paged_attention_calls"):
    globals()[_n] = _noted(_n)
'''


def test_new_architecture_module_is_found_and_used_end_to_end(tmp_path):
    root = tmp_path / "root"
    (root / "bench" / "archs").mkdir(parents=True)
    log = tmp_path / "calls.txt"
    (root / "bench" / "archs" / "toy.py").write_text(
        TOY.replace("@LOG@", repr(str(log))))
    cfg = _config(tmp_path, "ToyDecoderForTest", name="toy")
    with pytest.raises(manifest.ManifestError, match="ToyDecoderForTest"):
        model.load(cfg)                   # not in the benchmark's own dir
    r = harness.run(cfg, TINY_TRAFFIC, {"output_tok_s": "tokens/s",
                                        "setup_s": "s"}, {},
                    seed=6, seconds=1.0, trace=False, require_tpu=False,
                    cache_dir=str(tmp_path / "weights"), root=str(root))
    assert r["correct"] is True
    m = model.load(cfg, str(root))
    assert os.path.samefile(m.arch.__file__, root / "bench" / "archs" /
                            "toy.py")
    step = work.Step("decode", [(3, 1), (0, 1)], 0, 2)
    wb = work.Weights({n: 10 for n, _, _ in m.arch.layer_linears(m)}, 5)
    work.least_s(m, wb, step, work.peaks("TPU v5 lite"))
    w = harness.Window(model=m, weights=wb, batch=2)
    for name in ("icq_matmul_v2_roofline", "icq_dequant_v2_roofline",
                 "paged_attention_roofline"):
        spec = importlib.util.spec_from_file_location(
            "archs_" + name, manifest.layer_metric_path(name))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        reader.calls(w, step)
    called = set(log.read_text().split())
    assert called == {"block", "program_config", "tree", "leaf_shape",
                      "layer_bytes", "layer_weights", "layer", "step_flops",
                      "step_bytes", "matmul_calls", "dequant_calls",
                      "paged_attention_calls"}
