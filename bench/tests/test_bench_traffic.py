"""The traffic generator: determinism per seed, and its lengths."""
import collections
import os
from statistics import median

import numpy as np
import pytest

from bench import manifest, traffic

MIXES = ("chat", "rag", "longctx")


def _spec(name):
    return traffic.load(manifest.traffic_path(name))


def _stream(spec, seed, n):
    tr = traffic.Traffic(spec, seed, vocab=1000)
    return [tr.next(i % spec.concurrency) for i in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    spec = _spec(mix)
    seed = 2 ** 31 + 12345          # larger than 32 signed bits hold
    a, b = _stream(spec, seed, 40), _stream(spec, seed, 40)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_change_tokens_and_order_not_the_sizes(mix):
    """The seed picks token ids; the sizes, and their order, are the same
    for every seed, so a window does the same work whatever the seed."""
    spec = _spec(mix)
    a, b = _stream(spec, 1, 2 * spec.cycle), _stream(spec, 2, 2 * spec.cycle)
    assert any(not np.array_equal(x.prompt[:4], y.prompt[:4])
               for x, y in zip(a, b))
    assert ([(len(x.prompt), x.max_new) for x in a]
            == [(len(y.prompt), y.max_new) for y in b])
    # a cycle holds the stratified lengths, each once
    pa = collections.Counter(len(x.prompt) for x in a[: spec.cycle])
    assert pa == collections.Counter(traffic.stratified(spec.prompt,
                                                        spec.cycle))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_range(mix):
    spec = _spec(mix)
    for r in _stream(spec, 5, 3 * spec.cycle):
        assert spec.prompt["min"] <= len(r.prompt) <= spec.prompt["max"]
        assert 1 <= r.max_new <= spec.output["max"]
        assert len(r.prompt) + r.max_new <= spec.max_len
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000


def test_lognormal_median_and_uniform_span():
    for mix in MIXES:
        spec = _spec(mix)
        for dist in (spec.prompt, spec.output):
            xs = traffic.stratified(dist, 1001)
            assert abs(median(xs) - dist["median"]) <= 1
            assert min(xs) >= dist["min"] and max(xs) == dist["max"]
    uni = {"dist": "uniform", "min": 1024, "max": 3072}
    ys = traffic.stratified(uni, 2049)
    assert min(ys) == 1024 and max(ys) == 3072
    assert abs(median(ys) - 2048) <= 1


def test_every_mix_names_its_source(tmp_path):
    import json
    for mix in MIXES:
        assert "arXiv" in _spec(mix).source
    with open(manifest.traffic_path("chat")) as f:
        d = json.load(f)
    del d["source"]
    p = os.path.join(tmp_path, "x.json")
    with open(p, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="source"):
        traffic.load(p)


def test_first_requests_start_out_of_phase():
    spec = _spec("chat")
    tr = traffic.Traffic(spec, 9, vocab=100)
    firsts = [tr.next(c) for c in range(spec.concurrency)]
    later = [tr.next(c) for c in range(spec.concurrency)]
    assert len({r.max_new for r in firsts}) > spec.concurrency // 2
    assert sum(r.max_new for r in firsts) < sum(r.max_new for r in later)


def test_bad_mix_is_refused(tmp_path):
    import json
    with open(manifest.traffic_path("chat")) as f:
        d = json.load(f)
    d["max_len"] = 100
    p = os.path.join(tmp_path, "x.json")
    with open(p, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="fit"):
        traffic.load(p)
