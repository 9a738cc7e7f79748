"""The traffic generator: deterministic in the seed, different across
seeds, and the SAME multiset of sizes and gaps for every seed."""

import json
import os

import pytest

from benchmark.lib import traffic


def _mix(root, name):
    with open(os.path.join(root, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "decode-saturated"])
def test_same_seed_same_requests(root, name):
    a = traffic.serve_requests(_mix(root, name), 49152, 1234567, 30)
    b = traffic.serve_requests(_mix(root, name), 49152, 1234567, 30)
    assert a == b


@pytest.mark.parametrize("name", ["chat", "decode-saturated"])
def test_other_seed_other_order_same_sizes(root, name):
    mix = _mix(root, name)
    # past a closed loop's first round, whose outputs are cut short
    skip = mix.get("clients", 0)
    a = traffic.serve_requests(mix, 49152, 1, 30)[skip:]
    b = traffic.serve_requests(mix, 49152, 2 ** 31 + 11, 30)[skip:]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)


def test_open_loop_arrivals(root):
    mix = _mix(root, "chat")
    reqs = traffic.serve_requests(mix, 49152, 5, 30)
    horizon = 30 + mix["ramp_s"]
    assert len(reqs) == round(mix["rate_per_s"] * horizon)
    due = [r["due"] for r in reqs]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < horizon
    gaps_a = sorted(round(b - a, 9) for a, b in zip(due, due[1:]))
    due_b = [r["due"] for r in traffic.serve_requests(mix, 49152, 6, 30)]
    gaps_b = sorted(round(b - a, 9) for a, b in zip(due_b, due_b[1:]))
    # the same gaps but for the one that leads (it is dropped: first due 0)
    assert len(set(gaps_a) ^ set(gaps_b)) <= 2
    for r in reqs:
        assert 32 <= len(r["prompt"]) <= 3072 and 16 <= r["max_new"] <= 512
        assert r["max_new"] % mix["output_len_step"] == 0
        assert len(r["prompt"]) + r["max_new"] < mix["max_context"]
        assert min(r["prompt"]) >= 2 and max(r["prompt"]) < 49152


def test_closed_loop_clients_and_stagger(root):
    mix = _mix(root, "decode-saturated")
    reqs = traffic.serve_requests(mix, 49152, 5, 30)
    assert len(reqs) == mix["clients"] * mix["requests_per_client"]
    assert {r["client"] for r in reqs} == set(range(mix["clients"]))
    assert all(r["due"] is None for r in reqs)
    first = [r["max_new"] for r in reqs[:mix["clients"]]]
    assert first[0] == 64 and first[-1] >= 256     # spread completions
    for r in reqs[mix["clients"]:]:
        assert 128 <= len(r["prompt"]) <= 512 and 256 <= r["max_new"] <= 1024
    # output lengths take few values, and the warm-up can cover each
    lengths = traffic.distinct_max_new(reqs)
    assert all(v % mix["output_len_step"] == 0 for v in lengths)
    assert len(lengths) <= 1 + 1024 // mix["output_len_step"]


def test_quantiles_of_the_stated_distributions():
    ln = {"dist": "lognormal", "median": 384, "sigma": 0.9, "lo": 32,
          "hi": 3072}
    assert traffic.quantile(ln, 0.5) == pytest.approx(384, rel=1e-6)
    assert traffic.quantile(ln, 1e-9) == 32 and traffic.quantile(ln, 1 - 1e-9) == 3072
    assert traffic.quantile({"dist": "uniform", "lo": 10, "hi": 20}, 0.25) == 12.5
    assert traffic.quantile({"dist": "exponential", "mean": 2.0}, 0.5) == \
        pytest.approx(1.3862943611)
    assert traffic._norm_ppf(0.975) == pytest.approx(1.959964, abs=1e-5)
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
    # a gamma of cv 1 is the exponential; cv 3 is burstier (median far
    # under the mean); scipy's own quantile is the yardstick
    g1 = {"dist": "gamma", "mean": 2.0, "cv": 1.0}
    assert traffic.quantile(g1, 0.5) == pytest.approx(1.3862943611)
    from scipy.stats import gamma
    assert traffic.quantile({"dist": "gamma", "mean": 1.0, "cv": 3.0}, 0.9) \
        == pytest.approx(gamma.ppf(0.9, 1 / 9, scale=9.0))
    assert traffic.quantile({"dist": "gamma", "mean": 1.0, "cv": 3.0},
                            0.5) < 0.02


def test_a_table_of_values_is_a_distribution(tmp_path, monkeypatch):
    vals = {"dist": "table", "values": [30, 10, 20, 40]}
    assert [traffic.quantile(vals, p) for p in (0.1, 0.3, 0.6, 0.99)] == \
        [10, 20, 30, 40]
    (tmp_path / "lens.csv").write_text("5\n7\n6\n")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", str(tmp_path))
    assert traffic.quantile({"dist": "table", "file": "lens.csv"}, 0.5) == 6
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "table", "file": "../lens.csv"}, 0.5)


def test_bursty_arrivals_and_shared_prefixes_are_data(root):
    """The mixes PERF.md keeps for later (gamma arrivals, a Zipf pool of
    system prompts) need no new code: parameters of the one generator."""
    mix = _mix(root, "chat")
    mix.pop("shuffle_block")
    mix.update(arrival={"dist": "gamma", "cv": 3.0},
               prompt_len={"dist": "uniform", "lo": 64, "hi": 256},
               shared_prefix={"pool": 8, "zipf_s": 1.0,
                              "len": {"dist": "uniform", "lo": 1024,
                                      "hi": 2048}})
    a = traffic.serve_requests(mix, 49152, 3, 40)
    assert a == traffic.serve_requests(mix, 49152, 3, 40)
    b = traffic.serve_requests(mix, 49152, 4, 40)
    gaps = lambda rs: sorted(round(y["due"] - x["due"], 9)
                             for x, y in zip(rs, rs[1:]))
    ga = gaps(a)
    assert len(set(ga) ^ set(gaps(b))) <= 2       # same gaps, other order
    mean = sum(ga) / len(ga)
    cv = (sum((g - mean) ** 2 for g in ga) / len(ga)) ** 0.5 / mean
    assert 1.8 < cv < 3.5                         # an exponential has 1
    # eight heads, the most popular on about 1 / H(8) = 37% of the requests
    def popularity(rs):
        heads = {}
        for r in rs:
            key = tuple(r["prompt"][:1024])
            heads[key] = heads.get(key, 0) + 1
        return sorted(heads.values(), reverse=True)

    counts = popularity(a)
    assert len(counts) == 8 and counts[-1] >= 1
    assert counts[0] == round(len(a) / sum(1 / j for j in range(1, 9)))
    assert counts == popularity(b)                # whatever the seed
    for r in a:
        assert 1024 + 64 <= len(r["prompt"]) <= 2048 + 256
        assert len(r["prompt"]) + r["max_new"] < mix["max_context"]
    # the warm-up reaches the longest head plus the longest tail
    wave1, _ = traffic.warm_requests(mix, 49152, 3)
    assert max(len(r["prompt"]) for r in wave1) >= 2200


def test_warm_requests_cover_the_extremes(root):
    mix = _mix(root, "chat")
    wave1, wave2 = traffic.warm_requests(mix, 49152, 3)
    lens = [len(r["prompt"]) for r in wave1]
    assert min(lens) <= 40 and max(lens) >= 3000
    shared = wave2[0]["prompt"]
    base = wave1[len(wave1) // 2]["prompt"]
    assert shared[:len(base) - 3] == base[:len(base) - 3] and shared != base


def test_every_round_of_a_closed_loop_holds_the_same_sizes(root):
    """Whatever stretch of the clients' sequences a window uses, it sees the
    same sizes: each round of the 64 clients spans the distribution."""
    mix = _mix(root, "decode-saturated")
    k = mix["clients"]
    a = traffic.serve_requests(mix, 49152, 11, 40)
    b = traffic.serve_requests(mix, 49152, 12, 40)
    for rnd in range(3):
        ra, rb = a[rnd * k:(rnd + 1) * k], b[rnd * k:(rnd + 1) * k]
        assert sorted(len(r["prompt"]) for r in ra) == \
            sorted(len(r["prompt"]) for r in rb)
        if rnd:             # the first round's outputs are cut short
            assert sorted(r["max_new"] for r in ra) == \
                sorted(r["max_new"] for r in rb)
        assert [len(r["prompt"]) for r in ra] != [len(r["prompt"]) for r in rb]
        lens = sorted(len(r["prompt"]) for r in ra)
        assert lens[0] < 140 and lens[-1] > 500


def test_blocks_of_an_open_loop_hold_the_same_sizes(root):
    mix = _mix(root, "chat")
    blk = mix["shuffle_block"]
    a = traffic.serve_requests(mix, 49152, 21, 40)
    b = traffic.serve_requests(mix, 49152, 22, 40)
    for i in range(0, len(a) - blk + 1, blk):
        assert sorted(len(r["prompt"]) for r in a[i:i + blk]) == \
            sorted(len(r["prompt"]) for r in b[i:i + blk])
    # without blocks the whole sequence is one shuffle
    import random
    whole = traffic.stratified({"dist": "uniform", "lo": 0, "hi": 60}, 6,
                               random.Random(1))
    assert sorted(whole) == [5, 15, 25, 35, 45, 55]
    halves = traffic.stratified({"dist": "uniform", "lo": 0, "hi": 60}, 6,
                                random.Random(1), block=3)
    assert sorted(halves[:3]) == [5, 25, 45] and sorted(halves[3:]) == [15, 35, 55]
