"""The traffic schedule: fixed by the seed; every seed offers the same sizes
and gaps, the seed swapping neighbours in a base order."""
import numpy as np

from bench import loadgen

CHAT = {
    "arrivals": {"kind": "poisson", "rate_per_s": 5.0},
    "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 16, "max": 512},
    "output_len": {"dist": "uniform", "min": 32, "max": 256},
}
BIG_SEED = 2**31 + 12345


def _key(items):
    return [(it.due_s, tuple(it.prompt.tolist()), it.max_new)
            for it in items]


def test_same_seed_same_schedule():
    a = loadgen.schedule(CHAT, BIG_SEED, 20.0, 50272)
    b = loadgen.schedule(CHAT, BIG_SEED, 20.0, 50272)
    assert _key(a) == _key(b)


def _blocks(values):
    k = loadgen.SWAP_BLOCK
    return [sorted(values[s:s + k]) for s in range(0, len(values), k)]


def test_other_seed_other_order_same_work():
    a = loadgen.schedule(CHAT, BIG_SEED, 20.0, 50272)
    b = loadgen.schedule(CHAT, BIG_SEED + 1, 20.0, 50272)
    # another order: neighbours swapped ...
    for f in (lambda it: len(it.prompt), lambda it: it.max_new):
        assert [f(it) for it in a] != [f(it) for it in b]
        # ... only within each run of SWAP_BLOCK requests
        assert _blocks([f(it) for it in a]) == _blocks([f(it) for it in b])
    ga = np.diff([it.due_s for it in a] + [20.0])
    gb = np.diff([it.due_s for it in b] + [20.0])
    assert not np.allclose(ga, gb)
    np.testing.assert_allclose(np.concatenate(_blocks(list(ga))),
                               np.concatenate(_blocks(list(gb))), atol=1e-9)
    # other tokens; the high bits of the seed matter too
    assert all((x.prompt[:16] != y.prompt[:16]).any() for x, y in zip(a, b))
    c = loadgen.schedule(CHAT, BIG_SEED + 2**32, 20.0, 50272)
    assert _key(a) != _key(c)


def test_shuffle_blocks_keeps_each_run():
    rng = np.random.default_rng(3)
    v = np.arange(11)
    out = loadgen.shuffle_blocks(v, rng)
    assert _blocks(list(out)) == _blocks(list(v))
    assert list(v) == list(range(11))           # the input is left alone


def test_sizes_and_gaps_are_quantiles_of_the_mix():
    a = loadgen.schedule(CHAT, 1, 20.0, 50272)
    assert len(a) == 100
    assert sorted(len(it.prompt) for it in a) == \
        loadgen.quantiles(CHAT["prompt_len"], 100)
    assert sorted(it.max_new for it in a) == \
        loadgen.quantiles(CHAT["output_len"], 100)
    g = np.diff([it.due_s for it in a] + [20.0])
    np.testing.assert_allclose(sorted(g), sorted(
        loadgen.gaps(CHAT["arrivals"], 100, 20.0)), atol=1e-9)


def test_due_times_fill_the_window():
    items = loadgen.schedule(CHAT, 3, 30.0, 100)
    due = [it.due_s for it in items]
    assert due[0] == 0.0 and all(0 <= d < 30.0 for d in due)
    assert due == sorted(due)
    assert all(16 <= len(it.prompt) <= 512 for it in items)
    assert all(32 <= it.max_new <= 256 for it in items)
    assert all(0 <= t < 100 for it in items for t in it.prompt)


def test_quantiles_follow_the_distribution():
    q = loadgen.quantiles({"dist": "lognormal", "median": 128, "sigma": 0.7,
                           "min": 1, "max": 10**6}, 101)
    assert q[50] == 128
    u = loadgen.quantiles({"dist": "uniform", "min": 1024, "max": 1792}, 4)
    assert u == [1120, 1312, 1504, 1696]


def test_rate_override_changes_only_the_count():
    a = loadgen.schedule(CHAT, 4, 10.0, 1000)
    b = loadgen.schedule(CHAT, 4, 10.0, 1000, rate=8.0)
    assert len(a) == 50 and len(b) == 80


def test_warmup_runs_every_bucket():
    w = loadgen.warmup_prompts([32, 128, 512], 1000)
    assert [len(p) for p, _ in w] == [32, 128, 512]
    assert all(n == 2 for _, n in w)


def test_preroll_is_its_own_stream():
    a = loadgen.schedule(CHAT, 6, 10.0, 1000)
    b = loadgen.schedule(CHAT, 6, 10.0, 1000, part=1)
    assert [it.max_new for it in a] != [it.max_new for it in b]
    assert sorted(it.max_new for it in a) == sorted(it.max_new for it in b)
