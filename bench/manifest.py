"""``BENCHMARK.json``: load it, check it against the benchmark's contract,
and find the files that belong to a cell by name.

A cell ``<config>.<traffic>`` is served from four kinds of files, each
found by name so that a new configuration, architecture, traffic mix or
per-layer metric is added by adding a file:

  bench/configs/<config>.json        model sizes, quantization, check limit
  bench/archs/<module>.py            one module per architecture, found by
                                     the name the configuration publishes
                                     (``published.architectures[0]``)
                                     among those the module lists in its
                                     ``ARCHITECTURES``
  bench/traffic/<traffic>.json       loop, lanes, lengths, chunking
  bench/layer_metrics/<metric>.py    one reader per per-layer metric
"""
from __future__ import annotations

import glob
import hashlib
import importlib.util
import json
import os
import re
import sys
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(ValueError):
    pass


def _line(text, what: str) -> None:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        raise ManifestError(f"{what}: 1 to 200 characters on one line, "
                            f"no tab: {text!r}")


def _name(text, what: str) -> None:
    if not isinstance(text, str) or not NAME_RE.match(text):
        raise ManifestError(f"{what}: bad name {text!r}")


def _unique(items: List[dict], what: str) -> None:
    names = [it["name"] for it in items]
    dup = {n for n in names if names.count(n) > 1}
    if dup:
        raise ManifestError(f"duplicate {what} names: {sorted(dup)}")


def _extra(item: dict, allowed: set, what: str, optional=()) -> None:
    keys = set(item)
    missing = allowed - keys
    extra = keys - allowed - set(optional)
    if missing or extra:
        raise ManifestError(f"{what} {item.get('name')!r}: missing "
                            f"{sorted(missing)}, unknown {sorted(extra)}")


def validate(m: dict, root: str = ROOT) -> None:
    """Raise ManifestError where ``m`` breaks the contract or names a file
    that is not there."""
    if set(m) != TOP_KEYS:
        raise ManifestError(f"top-level keys must be {sorted(TOP_KEYS)}, "
                            f"got {sorted(m)}")
    paths = m["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for p in paths:
        if (not isinstance(p, str) or not PATH_RE.match(p)
                or p.startswith("/") or ".." in p.split("/")):
            raise ManifestError(f"paths: bad directory {p!r}")
    cmd = m["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        raise ManifestError("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command: path leaves the repo: {word!r}")
        if "/" in word and not any(word.startswith(p.rstrip("/") + "/")
                                   for p in paths):
            raise ManifestError(f"command names a file outside paths: "
                                f"{word!r}")
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        raise ManifestError("run_seconds: a whole number from 1 to 51")

    configs, cells = m["configs"], m["workloads"]
    e2e, layers = m["end_to_end"], m["per_layer"]
    for seq, lo, hi, what in ((configs, 1, 24, "configs"),
                              (cells, 1, 24, "workloads"),
                              (e2e, 1, 16, "end_to_end"),
                              (layers, 1, 128, "per_layer")):
        if not isinstance(seq, list) or not lo <= len(seq) <= hi:
            raise ManifestError(f"{what}: {lo} to {hi} entries")
        for it in seq:
            _name(it.get("name"), what)
        _unique(seq, what)
    _unique(e2e + layers, "metric")

    cfg_names = set()
    files = set()
    for c in configs:
        _extra(c, CONFIG_KEYS, "config")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        f = c["file"]
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            raise ManifestError(f"config file outside paths: {f!r}")
        if f in files:
            raise ManifestError(f"config file used twice: {f!r}")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            raise ManifestError(f"config file missing: {f!r}")
        with open(os.path.join(root, f)) as fh:
            try:
                architecture(architecture_name(json.load(fh), f), root)
            except json.JSONDecodeError as e:
                raise ManifestError(f"config file {f!r}: {e}") from None
        red = c["reduced"]
        if not isinstance(red, list) or len(red) > 16:
            raise ManifestError("reduced: a list of at most 16 keys")
        for k in red:
            _name(k, "reduced key")
        cfg_names.add(c["name"])

    used_cfgs = set()
    cell_names = set()
    pairs = set()
    for w in cells:
        _extra(w, WORKLOAD_KEYS, "workload")
        _name(w["config"], "workload config")
        _name(w["traffic"], "workload traffic")
        _line(w["why"], "workload why")
        if w["config"] not in cfg_names:
            raise ManifestError(f"workload {w['name']}: unknown config "
                                f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips must be 1 or 4")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise ManifestError(f"config and traffic pair twice: {pair}")
        pairs.add(pair)
        if w["name"] != f"{w['config']}.{w['traffic']}":
            raise ManifestError(f"workload {w['name']}: names are "
                                f"<config>.<traffic>")
        if not os.path.isfile(traffic_path(w["traffic"], root)):
            raise ManifestError(f"workload {w['name']}: no traffic file "
                                f"for {w['traffic']!r}")
        used_cfgs.add(w["config"])
        cell_names.add(w["name"])
    if used_cfgs != cfg_names:
        raise ManifestError(f"configs used by no cell: "
                            f"{sorted(cfg_names - used_cfgs)}")
    if sum(w["chips"] == 4 for w in cells) > max(1, len(cells) // 2):
        raise ManifestError("too many four-chip cells")

    def check_metric(x, keys, sources, what):
        _extra(x, keys, what, optional=("workloads",))
        if not isinstance(x["unit"], str) or not UNIT_RE.match(x["unit"]):
            raise ManifestError(f"{what} {x['name']}: bad unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            raise ManifestError(f"{what} {x['name']}: better is lower|higher")
        if x["source"] not in sources:
            raise ManifestError(f"{what} {x['name']}: bad source "
                                f"{x['source']!r}")
        ws = x.get("workloads")
        if ws is not None:
            if not isinstance(ws, list) or not ws:
                raise ManifestError(f"{what} {x['name']}: empty workloads")
            unknown = set(ws) - cell_names
            if unknown:
                raise ManifestError(f"{what} {x['name']}: unknown cells "
                                    f"{sorted(unknown)}")

    e2e_by = {}
    for x in e2e:
        check_metric(x, E2E_KEYS, E2E_SOURCES, "end_to_end")
        b = x["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            raise ManifestError(f"end_to_end {x['name']}: bound from 0.01 "
                                f"to 0.25")
        e2e_by[x["name"]] = x
    if "setup_s" not in e2e_by:
        raise ManifestError("end_to_end must have setup_s")

    def cells_of(x):
        return set(x.get("workloads", cell_names))

    layer_names = {}
    for x in layers:
        check_metric(x, LAYER_KEYS, SOURCES, "per_layer")
        _line(x["layer"], "layer")
        mv = e2e_by.get(x["moves"])
        if mv is None:
            raise ManifestError(f"per_layer {x['name']}: moves an unknown "
                                f"metric {x['moves']!r}")
        if not cells_of(x) <= cells_of(mv):
            raise ManifestError(
                f"per_layer {x['name']}: listed in cells that do not report "
                f"{x['moves']}: {sorted(cells_of(x) - cells_of(mv))}")
        if not os.path.isfile(layer_metric_path(x["name"], root)):
            raise ManifestError(f"per_layer {x['name']}: no reader file")
        layer_names[x["name"]] = x
    for cell in cell_names:
        others = [x for x in e2e if x["name"] != "setup_s"
                  and cell in cells_of(x)]
        if not others:
            raise ManifestError(f"cell {cell}: no end-to-end metric "
                                f"besides setup_s")
        if not any(cell in cells_of(x) for x in layers):
            raise ManifestError(f"cell {cell}: no per-layer metric")


def config_path(name: str, m: dict, root: str = ROOT) -> str:
    for c in m["configs"]:
        if c["name"] == name:
            return os.path.join(root, c["file"])
    raise KeyError(name)


def architecture_name(config: dict, where: str) -> str:
    """The architecture a configuration file publishes."""
    names = config.get("published", {}).get("architectures")
    if not isinstance(names, list) or not names or not isinstance(
            names[0], str):
        raise ManifestError(f"{where}: published.architectures must name "
                            f"the architecture")
    return names[0]


def _load_arch(path: str):
    """The module in ``path``, loaded once per process."""
    path = os.path.abspath(path)
    name = ("bench_arch_" + re.sub(r"\W", "_", os.path.basename(path)[:-3])
            + "_" + hashlib.sha1(path.encode()).hexdigest()[:8])
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def architecture(name: str, root: str = ROOT):
    """The one module of ``<root>/bench/archs`` whose ``ARCHITECTURES``
    lists ``name``. None or two is a ManifestError that names it."""
    files = sorted(glob.glob(os.path.join(root, "bench", "archs", "*.py")))
    mods = [_load_arch(f) for f in files
            if not os.path.basename(f).startswith("_")]
    found = [m for m in mods if name in getattr(m, "ARCHITECTURES", ())]
    if len(found) != 1:
        raise ManifestError(
            f"architecture {name!r}: claimed by "
            f"{[os.path.basename(m.__file__) for m in found]} in "
            f"bench/archs, which must hold exactly one module that lists "
            f"it in ARCHITECTURES")
    return found[0]


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def layer_metric_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "layer_metrics", f"{name}.py")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    validate(m, root)
    return m


def cell(m: dict, workload: str) -> Dict:
    """The workload entry and the end-to-end / per-layer metrics it
    reports, in manifest order."""
    for w in m["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{[w['name'] for w in m['workloads']]}")

    def mine(x):
        return workload in x.get("workloads", [workload])

    return dict(workload=w,
                end_to_end=[x for x in m["end_to_end"] if mine(x)],
                per_layer=[x for x in m["per_layer"] if mine(x)])
