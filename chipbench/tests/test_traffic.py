"""The traffic generator: seeds, fit, rates and lengths."""
import math

import numpy as np
import pytest

from chipbench import run, traffic


def _mixes():
    """Every mix file, with the configuration of a cell that runs it, or
    the first configuration file where no cell does yet."""
    used = {c["traffic"]: c["config"]
            for c in run.load_benchmark()["workloads"]}
    first = sorted(p.stem for p in run.CONFIG_DIR.glob("*.json"))[0]
    return [(p.stem, used.get(p.stem, first))
            for p in sorted(traffic.TRAFFIC_DIR.glob("*.json"))]


MIXES = _mixes()


def _items(name, conf_name, seed, n=300):
    spec = traffic.load(name)
    conf = run.load_config(conf_name)
    t = traffic.Traffic(spec, seed, vocab=conf["model"]["vocab"],
                        max_len=conf["serve"]["max_len"])
    return spec, conf, [t.item(i) for i in range(n)]


@pytest.mark.parametrize("mix,conf_name", MIXES)
def test_same_seed_same_requests(mix, conf_name):
    _, _, a = _items(mix, conf_name, 2 ** 31 + 5)
    _, _, b = _items(mix, conf_name, 2 ** 31 + 5)
    _, _, c = _items(mix, conf_name, 2 ** 31 + 6)
    key = lambda it: (it.prompt.tolist(), it.max_new, it.due_s)
    assert [key(i) for i in a] == [key(i) for i in b]
    assert [key(i) for i in a] != [key(i) for i in c]
    # another seed reorders the same work within every block
    blk = traffic.load(mix)["block"]
    sizes = lambda xs: (sorted(len(i.prompt) for i in xs),
                        sorted(i.max_new for i in xs),
                        len(xs))
    for k in range(0, len(a) - blk + 1, blk):
        assert sizes(a[k:k + blk]) == sizes(c[k:k + blk])


@pytest.mark.parametrize("mix,conf_name", MIXES)
def test_every_request_fits(mix, conf_name):
    spec, conf, items = _items(mix, conf_name, 3, n=2000)
    vocab, max_len = conf["model"]["vocab"], conf["serve"]["max_len"]
    for it in items:
        assert len(it.prompt) + it.max_new <= max_len
        assert spec["prompt"]["min"] <= len(it.prompt) <= spec["prompt"]["max"]
        assert spec["output"]["min"] <= it.max_new <= spec["output"]["max"]
        assert it.prompt.min() >= 0 and it.prompt.max() < vocab


def test_too_long_a_mix_is_refused():
    spec = dict(traffic.load("chat"), prompt={"dist": "uniform", "min": 900,
                                              "max": 1000})
    with pytest.raises(ValueError, match="max_len"):
        traffic.Traffic(spec, 1, vocab=10, max_len=1024)


@pytest.mark.parametrize("mix,conf_name", MIXES)
def test_open_loop_rate(mix, conf_name):
    spec = traffic.load(mix)
    conf = run.load_config(conf_name)
    rate = spec["rate_per_s"]
    for seed in (1, 2 ** 31 + 9):
        t = traffic.Traffic(spec, seed, vocab=conf["model"]["vocab"],
                            max_len=conf["serve"]["max_len"])
        horizon = 600.0
        n = 0
        while t.item(n).due_s < horizon:
            n += 1
        # a Poisson count's standard deviation is sqrt(rate * horizon)
        assert abs(n - rate * horizon) <= 4 * math.sqrt(rate * horizon)
        gaps = np.diff([0.0] + [it.due_s for it in t.items])
        assert (gaps > 0).all()
        assert abs(gaps.mean() * rate - 1) < 0.05


def test_exponential_lengths_keep_the_published_mean():
    """A block of an exponential length distribution has the mean it was
    given, but for the clips and rounding."""
    dist = {"dist": "exponential", "mean": 214.5, "min": 1, "max": 10 ** 6}
    qs = (np.arange(1000) + 0.5) / 1000
    lengths = [traffic.quantile(dist, q) for q in qs]
    assert abs(np.mean(lengths) / 214.5 - 1) < 0.01
    clipped = [traffic.quantile(dict(dist, max=768), q) for q in qs]
    assert max(clipped) == 768 and min(clipped) == 1
