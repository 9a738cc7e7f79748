"""Percentile, burst-share and flop/byte arithmetic on hand-made inputs."""

import pytest

from benchmark.lib import arith

SC2 = dict(hidden_size=3072, intermediate_size=12288, num_attention_heads=24,
           num_key_value_heads=2, num_hidden_layers=30, vocab_size=49152)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (95, 3.85),
                                    (100, 4.0)])
def test_percentile(q, want):
    assert arith.percentile([4, 1, 3, 2], q) == pytest.approx(want)


def test_percentile_edge_cases():
    assert arith.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_iqr_share_is_the_drivers_rule():
    import statistics
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert arith.iqr_share(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def test_burst_shared_gaps_single_tokens():
    assert arith.burst_shared_gaps([0.0, 0.01, 0.03]) == \
        pytest.approx([0.01, 0.02])


def test_burst_shared_gaps_share_a_volley():
    # 4 tokens flushed together 40 ms after the last: 10 ms each, not 40+0+0+0
    t = [0.0, 0.040, 0.0401, 0.0402, 0.0403, 0.050]
    gaps = arith.burst_shared_gaps(t, burst_eps_s=0.0005)
    assert len(gaps) == 5
    assert gaps[:4] == pytest.approx([0.010075] * 4)
    assert sum(gaps) == pytest.approx(t[-1] - t[0])
    assert arith.burst_shared_gaps([1.0]) == []


def test_starcoder2_3b_parameter_counts():
    mm = arith.lm_matmul_params(SC2)
    assert mm["per_layer"] == 2 * 3072 * 3072 + 2 * 3072 * 256 \
        + 2 * 3072 * 12288
    assert mm["head"] == 3072 * 49152
    # 3.18 B with the untied head (ISSUE 25): 302.0 M outside the layers
    n = arith.lm_param_count(SC2)
    assert 3.17e9 < n < 3.19e9
    outside = arith.lm_param_count(dict(SC2, num_hidden_layers=0))
    assert outside == 2 * 49152 * 3072 + 2 * 3072
    per_layer = (n - outside) / 30
    assert 95.9e6 < per_layer < 96.2e6


def test_train_flops_per_token():
    cfg = dict(SC2, num_hidden_layers=2)
    mm = arith.lm_matmul_params(cfg)["total"]
    got = arith.train_flops_per_token(cfg, 4096)
    attn = 4 * 3072 * 4096 * 0.5 * 3.5 * 2
    assert got == pytest.approx(6 * mm + attn)
    assert 2.0e9 < got < 2.4e9          # the issue's 2.2 GFLOP a token


def test_flash_train_cost_is_compute_bound_at_4k():
    c = arith.flash_train_cost(SC2, batch=2, seq_len=4096)
    assert c["flops"] == pytest.approx(7 * 2 * 0.5 * 2 * 24 * 4096 ** 2 * 128)
    r = arith.roofline_share(c["flops"], c["bytes"], 0.01, PEAKS)
    assert r["bound"] == "compute"
    assert r["share_pct"] == pytest.approx(100 * c["flops"] / 197e12 / 0.01)


def test_paged_decode_cost_is_memory_bound():
    c = arith.paged_decode_cost(SC2, live_tokens=64 * 1000, rows=64)
    assert c["bytes"] == pytest.approx(64000 * 2 * 2 * 128 * 2
                                       + 64 * 24 * 128 * 4)
    assert c["flops"] == pytest.approx(4 * 24 * 128 * 64000)
    assert arith.roofline_share(c["flops"], c["bytes"], 1e-3,
                                PEAKS)["bound"] == "memory"


def test_a_share_above_what_the_chip_can_give_fails_loudly():
    assert arith.check_share("x", 99.0) == 99.0
    assert arith.check_share("x", 104.9) == 104.9
    with pytest.raises(RuntimeError, match="above what the chip can give"):
        arith.check_share("mfu.train", 105.1)


def test_window_metrics_on_hand_made_arrivals():
    """Two requests, tokens 0.1 s apart; one is due before the window."""
    reqs = [{"due_at": 9.0, "times": [9.5 + 0.1 * i for i in range(20)]},
            {"due_at": 10.2, "times": [10.5 + 0.1 * i for i in range(10)]},
            {"due_at": 10.9, "times": []}]
    m = arith.window_metrics(reqs, 10.0, 11.0, 0.0005)
    # the first request's tokens at 10.0 .. 10.9 (or 10.9 lost to rounding),
    # the second's at 10.5 .. 10.9
    assert m["output_tokens"] in (14, 15, 16)
    assert m["output_tokens_per_s"] == m["output_tokens"] / 1.0
    assert m["itl_p95_ms"] == pytest.approx(100.0, rel=1e-6)
    assert m["n_gaps"] == m["output_tokens"] - 1     # one first token
    assert m["n_ttft"] == 1                          # due in the window
    assert m["ttft_p95_ms"] == pytest.approx(300.0)
    empty = arith.window_metrics(reqs, 20.0, 21.0, 0.0005)
    assert empty["output_tokens"] == 0 and empty["itl_p95_ms"] is None


def test_the_senders_lateness_limit_follows_the_mix(bench):
    serve = bench.kind("serve")
    assert serve.late_limit_ms({"rate_per_s": 1.2}) == pytest.approx(16.6667,
                                                                     rel=1e-4)
    assert serve.late_limit_ms({"rate_per_s": 20.0}) == pytest.approx(1.0)
