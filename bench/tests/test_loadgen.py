"""The traffic generator: the same seed gives the same inputs, and every
seed offers the same work in another order."""
import numpy as np
import pytest

from bench import loadgen

OPEN = {"loop": "open", "rate_qps": 500.0,
        "tokens": {"dist": "zipf", "a": 1.3},
        "lengths": {"dist": "lognormal", "median": 60, "sigma": 0.5,
                    "lo": 8, "hi": 96},
        "warmup_s": 1, "shape_seed": 7}
SEEDS = (2 ** 31 + 11, 3)


def take(stream, n):
    return [stream[i] for i in range(n)]


def test_same_seed_same_inputs():
    a = take(loadgen.Stream(OPEN, SEEDS[0], 1000), 5000)
    b = take(loadgen.Stream(OPEN, SEEDS[0], 1000), 5000)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    np.testing.assert_array_equal(loadgen.arrivals(OPEN, SEEDS[0], 10.0),
                                  loadgen.arrivals(OPEN, SEEDS[0], 10.0))


def test_seeds_reorder_one_multiset_of_lengths_and_gaps():
    n = loadgen.POOL
    la = sorted(len(q) for q in take(loadgen.Stream(OPEN, SEEDS[0], 1000), n))
    lb = sorted(len(q) for q in take(loadgen.Stream(OPEN, SEEDS[1], 1000), n))
    assert la == lb
    ta = loadgen.arrivals(OPEN, SEEDS[0], 60.0)
    tb = loadgen.arrivals(OPEN, SEEDS[1], 60.0)
    assert not np.array_equal(ta, tb)
    # a whole pool of gaps spans the same time whatever its order: the
    # seeds differ only in the part of a pool that the horizon cuts
    assert abs(len(ta) - len(tb)) <= 0.01 * len(ta)
    k = loadgen.POOL
    assert ta[k - 1] == pytest.approx(tb[k - 1], rel=1e-9)
    assert len(ta) == pytest.approx(500 * 60, rel=0.03)


def test_lengths_and_tokens_stay_inside_the_mix():
    s = loadgen.Stream(OPEN, SEEDS[0], 50)
    qs = take(s, 6000)
    lens = np.array([len(q) for q in qs])
    assert lens.min() >= 8 and lens.max() <= 96
    assert 50 <= np.median(lens) <= 70
    toks = np.concatenate(qs)
    assert toks.min() >= 1 and toks.max() <= 49 and toks.dtype == np.int32
    assert set(s.lengths_used()) == set(lens) | set(s.lengths_used())


def test_fixed_lengths_and_uniform_tokens():
    mix = dict(OPEN, lengths={"dist": "fixed", "value": 75, "lo": 75,
                              "hi": 75}, tokens={"dist": "uniform"})
    qs = take(loadgen.Stream(mix, SEEDS[0], 1000), 3000)
    assert {len(q) for q in qs} == {75}
    toks = np.concatenate(qs)
    assert toks.min() == 1 and toks.max() == 999
    # every id about equally often: no rank dominates as under Zipf
    counts = np.bincount(toks, minlength=1000)[1:]
    assert counts.max() < 2 * counts.mean()


def test_normal_lengths_round_and_clip():
    mix = dict(OPEN, lengths={"dist": "normal", "mean": 75, "sd": 7.5,
                              "lo": 8, "hi": 96})
    pool = loadgen.length_pool(mix)
    assert pool.min() >= 8 and pool.max() <= 96
    assert np.mean(pool) == pytest.approx(75, abs=0.5)


def test_bursts_raise_the_rate_inside_them():
    mix = dict(OPEN, bursts={"every_s": 10, "len_s": 3, "mult": 4})
    t = loadgen.arrivals(mix, SEEDS[0], 40.0)
    phase = t % 10
    inside = np.count_nonzero(phase < 3) / (4 * 3)
    outside = np.count_nonzero(phase >= 3) / (4 * 7)
    assert inside / outside == pytest.approx(4, rel=0.1)
    assert outside == pytest.approx(500, rel=0.1)


def test_repeats_draw_from_a_pool_of_unique_queries():
    mix = dict(OPEN, repeats={"unique": 64, "alpha": 1.1})
    qs = take(loadgen.Stream(mix, SEEDS[0], 1000), 2000)
    distinct = {q.tobytes() for q in qs}
    assert len(distinct) <= 64
    top = max(sum(q is r for r in qs) for q in qs[:50])
    assert top > 2000 / 64              # rank 1 is drawn far above uniform


@pytest.mark.parametrize("change", [{"rate_qps": 0}, {"loop": "paced"},
                                    {"tokens": {"dist": "words"}}],
                         ids=["no_rate", "no_loop", "no_token_law"])
def test_a_mix_without_a_loop_or_rate_is_refused(tmp_path, change):
    import json

    bad = tmp_path / "m.json"
    bad.write_text(json.dumps(dict(OPEN, **change)))
    with pytest.raises(ValueError):
        loadgen.load_mix(bad)
