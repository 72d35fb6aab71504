"""BENCHMARK.json against the contract, and cells found by name."""
import copy
import json
import os
import shutil

import pytest

from bench import manifest

ROOT = manifest.ROOT


@pytest.fixture
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_manifest_is_valid(m):
    manifest.validate(m, ROOT)
    assert manifest.load(ROOT)["paths"] == ["bench"]


def test_every_cell_reports_setup_another_metric_and_a_layer(m):
    for w in m["workloads"]:
        c = manifest.cell(m, w["name"])
        names = [x["name"] for x in c["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        assert c["per_layer"]


@pytest.mark.parametrize("where,bad", [
    ("metric_name", "tokens per s"),
    ("metric_name", "mu/s"),
    ("unit", "tokens per second"),
    ("unit", "µs"),
    ("cell_name", "internlm2-2b,chat"),
])
def test_bad_characters_are_refused(m, where, bad):
    m = copy.deepcopy(m)
    if where == "metric_name":
        m["per_layer"][0]["name"] = bad
    elif where == "unit":
        m["end_to_end"][0]["unit"] = bad
    else:
        m["workloads"][0]["name"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m, ROOT)


def test_moves_must_be_reported_in_the_metrics_cells(m):
    m = copy.deepcopy(m)
    x = next(x for x in m["per_layer"] if x["moves"] == "prefill_tok_s")
    x["workloads"] = ["internlm2-2b.chat"]      # chat has no prefill_tok_s
    with pytest.raises(manifest.ManifestError, match="do not report"):
        manifest.validate(m, ROOT)


def test_moves_must_name_an_end_to_end_metric(m):
    m = copy.deepcopy(m)
    m["per_layer"][0]["moves"] = "no_such_metric"
    with pytest.raises(manifest.ManifestError, match="unknown metric"):
        manifest.validate(m, ROOT)


def test_unknown_cell_in_workloads_is_refused(m):
    m = copy.deepcopy(m)
    m["per_layer"][0]["workloads"] = ["internlm2-2b.nothing"]
    with pytest.raises(manifest.ManifestError, match="unknown cells"):
        manifest.validate(m, ROOT)


def test_extra_key_on_a_metric_is_refused(m):
    m = copy.deepcopy(m)
    m["end_to_end"][0]["why"] = "because"
    with pytest.raises(manifest.ManifestError, match="unknown"):
        manifest.validate(m, ROOT)


def test_bound_over_a_quarter_is_refused(m):
    m = copy.deepcopy(m)
    m["end_to_end"][0]["bound"] = 0.3
    with pytest.raises(manifest.ManifestError, match="bound"):
        manifest.validate(m, ROOT)


def _tree(tmp_path, m):
    """A copy of the benchmark's files under tmp_path."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".jax_cache",
                                                  ".trace", "probe",
                                                  "__pycache__"))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    return str(tmp_path)


@pytest.mark.parametrize("missing", ["config", "traffic", "layer_metric"])
def test_missing_file_of_a_cell_is_refused(tmp_path, m, missing):
    root = _tree(tmp_path, m)
    if missing == "config":
        os.remove(os.path.join(root, m["configs"][0]["file"]))
    elif missing == "traffic":
        os.remove(manifest.traffic_path(m["workloads"][0]["traffic"], root))
    else:
        os.remove(manifest.layer_metric_path(m["per_layer"][0]["name"], root))
    with pytest.raises(manifest.ManifestError, match="missing|no "):
        manifest.validate(m, root)


def test_new_traffic_file_is_found_without_a_code_edit(tmp_path, m):
    """A cell over a new mix needs a data file and a manifest entry."""
    m = copy.deepcopy(m)
    root = _tree(tmp_path, m)
    src = manifest.traffic_path("chat", root)
    with open(src) as f:
        t = json.load(f)
    t["prompt"] = {"dist": "uniform", "min": 64, "max": 256}
    with open(manifest.traffic_path("chat-short", root), "w") as f:
        json.dump(t, f)
    m["workloads"].append(dict(m["workloads"][0], name="internlm2-2b."
                               "chat-short", traffic="chat-short"))
    for x in m["end_to_end"] + m["per_layer"]:
        if "internlm2-2b.chat" in x.get("workloads", []):
            x["workloads"].append("internlm2-2b.chat-short")
    manifest.validate(m, root)
    c = manifest.cell(m, "internlm2-2b.chat-short")
    assert c["workload"]["traffic"] == "chat-short"
    from bench import traffic
    spec = traffic.load(manifest.traffic_path("chat-short", root))
    assert spec.prompt["max"] == 256


def test_unknown_workload_raises(m):
    with pytest.raises(KeyError):
        manifest.cell(m, "nope.nope")
