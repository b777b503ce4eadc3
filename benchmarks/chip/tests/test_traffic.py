"""The generator: deterministic per seed, and a seed changes the order of
the work, never its amount."""
import numpy as np

from chipbench import traffic as T

MIX = {"loop": "open", "task": "classify", "rate_rps": 50.0,
       "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.9,
                      "min": 16, "max": 512}}
BIG = 2 ** 31 + 12345


def test_same_seed_same_inputs():
    a = T.make_arrivals(MIX, BIG, 1000, 200)
    b = T.make_arrivals(MIX, BIG, 1000, 200)
    assert [x.t for x in a] == [x.t for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    a = T.make_arrivals(MIX, 1, 1000, 300)
    b = T.make_arrivals(MIX, 2, 1000, 300)
    la = sorted(len(x.tokens) for x in a)
    assert la == sorted(len(x.tokens) for x in b)
    assert abs(a[-1].t - b[-1].t) < 1e-9          # same gaps, summed
    assert [len(x.tokens) for x in a] != [len(x.tokens) for x in b]


def test_lengths_heavy_tailed_within_bounds():
    q = T.quantiles(MIX["prompt_len"], 1000)
    assert q.min() >= 16 and q.max() <= 512
    assert 55 <= np.median(q) <= 75
    assert np.percentile(q, 99) > 4 * np.median(q)


def test_poisson_rate():
    gaps = T.poisson_gaps(50.0, 4000)
    assert abs(gaps.mean() - 1 / 50.0) < 0.002
    assert abs(np.median(gaps) - np.log(2) / 50.0) < 0.001


def test_unknown_forms_refused():
    for mix in (dict(MIX, loop="closed"),
                dict(MIX, prompt_len=dict(MIX["prompt_len"], dist="uniform"))):
        try:
            T.make_arrivals(mix, 3, 100, 20)
        except ValueError:
            continue
        raise AssertionError(f"accepted {mix}")
