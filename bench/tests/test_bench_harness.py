"""A whole run at CPU size, with the chip check skipped: the result line,
the refusal without a chip, and ``correct`` coming out false when the
timed path is broken or computed one precision step lower."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import check, harness, manifest, model

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.json")
TINY_TRAFFIC = os.path.join(HERE, "data", "tiny_traffic.json")
E2E = {"output_tok_s": "tokens/s", "itl_p95_ms": "ms", "ttft_p95_ms": "ms",
       "prefill_tok_s": "tokens/s", "setup_s": "s"}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("weights"))


def _run(cache, seed=3, seconds=1.0):
    return harness.run(TINY, TINY_TRAFFIC, E2E, {}, seed=seed,
                       seconds=seconds, trace=False, require_tpu=False,
                       cache_dir=cache)


def test_tiny_run_is_correct_and_well_formed(cache):
    r = _run(cache)
    assert r["correct"] is True
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) == set(E2E)
    for v in r["metrics"].values():
        assert v["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    gap = r["check"]["logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    json.dumps(r, allow_nan=False)


def _alter_tokens(monkeypatch, vocab):
    """Every token the step programs produce is moved by one id."""
    from repro.serving import engine

    def wrap(make):
        def patched(cfg, sample=True, check=False):
            f = make(cfg, sample=sample, check=check)

            def g(*a, **k):
                out = f(*a, **k)
                return ((out[0] + 1) % vocab,) + tuple(out[1:])
            return g
        return patched

    monkeypatch.setattr(engine, "make_serving_step",
                        wrap(engine.make_serving_step))
    monkeypatch.setattr(engine, "make_fused_serving_step",
                        wrap(engine.make_fused_serving_step))


def test_altered_token_is_not_correct(cache, monkeypatch):
    _alter_tokens(monkeypatch, model.load(TINY).vocab)
    r = _run(cache, seed=4)
    assert r["correct"] is False
    gap = r["check"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_one_precision_lower_is_not_correct(cache):
    """The reference in float8 put in the program's place: at the same
    prompts and served tokens, the tokens it puts first lie further below
    the float32 reference's best than the limit allows, so the harness's
    own verdict on it is false while the program's is true."""
    r = harness.run(TINY, TINY_TRAFFIC, E2E, {}, seed=5, seconds=1.0,
                    trace=False, require_tpu=False, cache_dir=cache,
                    control=True)
    assert r["correct"] is True
    assert r["control"]["correct"] is False
    assert r["control"]["logit_gap"] > r["check"]["logit_gap"]["limit"]
    assert list(r)[-1] == "check"


def test_sample_holds_the_longest_and_is_seeded():
    recs = [dict(idx=i, prompt=[0] * (i % 7 + 1), served=[1] * (i % 5 + 1))
            for i in range(30)]
    a = check.sample(recs, 4, 11)
    assert len(a) == 4
    longest = max(recs, key=lambda r: len(r["prompt"]) + len(r["served"]))
    assert longest in a
    assert [r["idx"] for r in a] == [r["idx"] for r in check.sample(recs,
                                                                    4, 11)]
    assert check.sample([], 4, 1) == []


def test_refuses_a_host_without_a_chip(cache):
    with pytest.raises(harness.NoChip):
        harness.run(TINY, TINY_TRAFFIC, E2E, {}, seed=1, seconds=1.0,
                    trace=False, require_tpu=True, cache_dir=cache)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-2b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_prints_no_result_without_a_chip():
    p = _cli(manifest.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_command_fails_in_a_tree_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".jax_cache",
                                                  ".trace", "probe",
                                                  "__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
