"""The traffic generator: deterministic per seed, the same work for every
seed, and the distribution its file states."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loop, traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
ALL = sorted(f.stem for f in MIXES.glob("*.json"))


@pytest.mark.parametrize("mix", ALL)
def test_deterministic_per_seed(mix):
    spec = traffic.load(MIXES / f"{mix}.json")
    a = traffic.generate(spec, 2**33 + 5, 30, 1000)
    b = traffic.generate(spec, 2**33 + 5, 30, 1000)
    c = traffic.generate(spec, 7, 30, 1000)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]


@pytest.mark.parametrize("mix", ALL)
def test_every_seed_draws_the_same_work(mix):
    spec = traffic.load(MIXES / f"{mix}.json")
    runs = [traffic.generate(spec, s, 30, 1000) for s in (1, 2, 3)]
    for field in ("max_new",):
        sets = [sorted(getattr(x, field) for x in r) for r in runs]
        assert sets[0] == sets[1] == sets[2]
    lens = [sorted(len(x.prompt) for x in r) for r in runs]
    assert lens[0] == lens[1] == lens[2]
    if spec["loop"] == "open":
        gaps = [sorted(np.round(np.diff([0.0] + [x.due for x in r]), 9))
                for r in runs]
        assert gaps[0] == gaps[1]


@pytest.mark.parametrize("mix", ALL)
def test_matches_its_file(mix):
    spec = json.loads((MIXES / f"{mix}.json").read_text())
    items = traffic.generate(spec, 11, 200, 1000)
    for key, got in (("prompt_len", [len(x.prompt) for x in items]),
                     ("output_len", [x.max_new for x in items])):
        d = spec[key]
        assert min(got) >= d["min"] and max(got) <= d["max"]
        assert abs(np.median(got) / d["median"] - 1) < 0.05
        inner = np.log([g for g in got if d["min"] < g < d["max"]])
        assert abs(np.std(np.log(got)) - d["sigma"]) < 0.2 * d["sigma"] \
            or len(inner) < len(got) / 2
    assert all(1 <= t < 1000 for x in items for t in x.prompt)
    if spec["loop"] == "open":
        n = len(items)
        assert n == round(spec["rate_per_s"] * 200)
        assert abs(items[-1].due / (n / spec["rate_per_s"]) - 1) < 0.05
        assert all(b.due >= a.due for a, b in zip(items, items[1:]))
    else:
        assert len(items) == spec["pool"]


def test_blocks_hold_even_work():
    """Every block of 8 consecutive requests holds a spread of the whole
    distribution, whatever the seed."""
    spec = traffic.load(MIXES / "decode_open.json")
    n = traffic.count(spec, 51)
    nb = -(-n // 8)
    sums = []
    for seed in (1, 2, 3):
        items = traffic.generate(spec, seed, 51, 1000)
        assert len(items) == n
        out = np.array([x.max_new for x in items])
        sizes = [len(b) for b in np.array_split(np.arange(n), nb)]
        sums.append(sorted(np.add.reduceat(out, np.cumsum([0] + sizes[:-1]))))
    total = sum(sums[0])
    # no block holds more than twice its share of the answer tokens
    assert all(max(s) < 2 * total / nb for s in sums)


def test_buckets_cover_the_prompt_range():
    spec = traffic.load(MIXES / "decode_open.json")
    assert traffic.buckets(spec, lambda n: loop.bucket_of(n, 1280)) == \
        [16, 32, 64, 128, 256]


def test_prompt_open_draws_inside_its_ranges_and_the_4k_buckets():
    spec = traffic.load(MIXES / "prompt_open.json")
    bucket = lambda n: loop.bucket_of(n, 4224)          # noqa: E731
    assert traffic.buckets(spec, bucket) == [256, 512, 1024, 2048, 4096]
    for seed in (1, 2**33 + 5):
        items = traffic.generate(spec, seed, 51, 151936)
        plen = [len(x.prompt) for x in items]
        olen = [x.max_new for x in items]
        assert 256 <= min(plen) and max(plen) <= 4096
        assert 8 <= min(olen) and max(olen) <= 128
        assert {bucket(n) for n in plen} <= {256, 512, 1024, 2048, 4096}
        assert max(plen) + max(olen) <= 4224


@pytest.mark.parametrize("order, same", [("fixed", True), ("seed", False)])
def test_a_fixed_order_is_every_seeds(order, same):
    """``"order": "fixed"`` sends the same lengths and gaps in the same
    order for every seed, and only the token ids change; the seed's order
    is the default."""
    spec = dict(traffic.load(MIXES / "prompt_open.json"), order=order)
    if order == "seed":
        del spec["order"]
    a, b = (traffic.generate(spec, s, 51, 151936) for s in (3, 2**33 + 9))
    shape = [[(x.due, len(x.prompt), x.max_new) for x in r] for r in (a, b)]
    assert (shape[0] == shape[1]) == same
    assert [x.prompt for x in a] != [x.prompt for x in b]


def test_an_unknown_order_is_refused(tmp_path):
    spec = json.loads((MIXES / "prompt_open.json").read_text())
    (tmp_path / "m.json").write_text(json.dumps(dict(spec, order="trace")))
    with pytest.raises(ValueError, match="order"):
        traffic.load(tmp_path / "m.json")


def test_bucket_rule_is_the_engines():
    from types import SimpleNamespace

    from repro.serving.engine import ServingEngine
    for cap in (64, 1280):
        eng = SimpleNamespace(_bucket_cap=cap)
        for n in (1, 7, 8, 9, 16, 17, 100, 256, 1000):
            assert loop.bucket_of(n, cap) == \
                ServingEngine._bucket_len(eng, n)


@pytest.mark.parametrize("mix", ALL)
def test_the_last_block_is_the_same_for_every_seed(mix):
    """What arrives in a window's last seconds, and is cut by its close, is
    one fixed block; the requests before it come in the seed's order, or
    in the one fixed order of an ``"order": "fixed"`` mix."""
    spec = traffic.load(MIXES / f"{mix}.json")
    runs = [traffic.generate(spec, s, 51, 1000) for s in (1, 2**33 + 5)]
    n = len(runs[0])
    tail = n // -(-n // traffic.BLOCK)

    def shape(r):
        gaps = np.diff([0.0] + [x.due for x in r])
        return [(round(g, 9), len(x.prompt), x.max_new)
                for g, x in zip(gaps, r)]
    a, b = (shape(r) for r in runs)
    assert a[-tail:] == b[-tail:]
    assert (a[:-tail] == b[:-tail]) == (spec.get("order") == "fixed")


@pytest.mark.parametrize("mix", ALL)
def test_the_last_block_ranks_lengths_and_gaps_apart(mix):
    """The fixed last block orders prompt lengths, answer lengths and gaps
    each its own way: the longest prompt does not always carry the longest
    answer after the longest gap."""
    spec = traffic.load(MIXES / f"{mix}.json")
    r = traffic.generate(spec, 2**33 + 5, 51, 1000)
    n = len(r)
    tail = n // -(-n // traffic.BLOCK)

    def ranks(v):
        return tuple(np.argsort(np.argsort(v[-tail:], kind="stable"),
                                kind="stable"))
    orders = [ranks([len(x.prompt) for x in r]),
              ranks([x.max_new for x in r])]
    if spec["loop"] == "open":
        orders.append(ranks(np.diff([0.0] + [x.due for x in r])))
    assert len(set(orders)) == len(orders)
