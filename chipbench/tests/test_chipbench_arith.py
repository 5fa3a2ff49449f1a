"""The benchmark's arithmetic: window metrics from request timestamps, and
the dense reference module's operation and byte counts against numbers
worked by hand."""

import json
import math

import pytest

from chipbench import bench, e2e, flops, generator
from chipbench.e2e import Rec


def recs():
    # window [10, 20]; due times and token times in seconds
    return [
        Rec(0, due=2.0, admitted=2.5, token_times=[3.0, 11.0, 12.0, 13.0]),
        Rec(1, due=10.5, admitted=11.0, token_times=[12.5, 14.5, 16.5]),
        Rec(2, due=15.0, admitted=19.0, token_times=[21.0, 22.0]),
        Rec(3, due=18.0),                      # never admitted
        Rec(4, due=19.5, admitted=19.6, token_times=[19.8]),
        Rec(5, due=25.0, admitted=25.0, token_times=[26.0]),
    ]


def test_tokens_counted_inside_the_window_only():
    # rec0: 11, 12, 13; rec1: 12.5, 14.5, 16.5; rec4: 19.8 -> 7
    assert e2e.tokens_in(recs(), 10.0, 20.0) == 7
    m = e2e.end_to_end(recs(), 10.0, 20.0)
    assert m["tokens_per_s"] == pytest.approx(0.7)


def test_ttft_tail_is_censored_at_the_close():
    # due in window: rec1 (2.0), rec2 (first token after the close: enters
    # at 20 - 15 = 5.0), rec3 (none: 20 - 18 = 2.0), rec4 (0.3)
    assert e2e.ttfts(recs(), 10.0, 20.0) == pytest.approx([2.0, 5.0, 2.0,
                                                           0.3])
    # p95 of [0.3, 2, 2, 5] by linear interpolation: 2 + 0.85 * 3
    m = e2e.end_to_end(recs(), 10.0, 20.0)
    assert m["ttft_p95_ms"] == pytest.approx(4550.0)


def test_tpot_per_request_inside_the_window():
    # rec0: 11, 12, 13 -> 1.0; rec1: 12.5, 14.5, 16.5 -> 2.0; rec4 has one
    assert e2e.tpots(recs(), 10.0, 20.0) == pytest.approx([1.0, 2.0])
    m = e2e.end_to_end(recs(), 10.0, 20.0)
    assert m["tpot_p95_ms"] == pytest.approx(1950.0)


def test_percentile_matches_linear_interpolation():
    assert e2e.percentile([1, 2, 3, 4, 5], 50) == 3
    assert e2e.percentile([10], 95) == 10
    assert math.isnan(e2e.percentile([], 95))


def conf(name):
    return json.loads((bench.HERE / "configs" / f"{name}.json").read_text())


def test_deepseek_counts_by_hand():
    c = conf("deepseek-7b-pp2")
    ref = bench.reference(c)
    # q, k, v, o: 4 * 4096^2 = 67,108,864; MLP 3 * 4096 * 11008 =
    # 135,266,304
    assert ref.layer_matmul_params(c) == 202_375_168
    assert ref.layer_params(c) == 202_375_168 + 2 * 4096
    # one row at context 100: 2 * (15 layers + head 4096 * 102400) + QK and
    # PV 4 * 15 * 4096 * 100
    assert ref.decode_flops(c, [100]) == 6_934_691_840
    # bf16: weights + final norm + head + one embedding row + K and V of
    # 100 positions in 15 layers (32 heads x 128)
    assert ref.decode_bytes(c, [100]) == 6_934_953_984
    assert ref.prefill_flops(c, 128) == 779_988_500_480


def test_qwen_counts_by_hand():
    c = conf("qwen2-72b-tp4")
    ref = bench.reference(c)
    # q, o 8192^2 each; k, v 8192 x 1024 each (8 KV heads x 128); MLP
    # 3 * 8192 * 29568
    assert ref.layer_matmul_params(c) == 877_658_112
    # two norms (2 x 8192) and the q, k, v bias (8192 + 2 x 1024)
    assert ref.layer_params(c) == 877_684_736
    assert ref.decode_flops(c, [10, 20]) == 75_215_142_912
    assert ref.decode_bytes(c, [10, 20]) == 37_601_312_768


def test_least_time_takes_the_larger_bound():
    peaks = bench.peaks_for("TPU v5 lite")
    assert flops.least_time(0.0, 819e9, peaks) == pytest.approx(1.0)
    assert flops.least_time(197e12, 0.0, peaks, chips=4) == \
        pytest.approx(0.25)


def test_generator_sends_the_same_work_in_another_order():
    mix = generator.load_mix("chat")
    a = generator.requests(mix, 0.2, 51, 1, 8)
    b = generator.requests(mix, 0.2, 51, 2 ** 40 + 3, 8)
    assert len(a) == len(b) == round(0.2 * (mix["preroll_s"] + 51))
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert a[-1].arrival == pytest.approx(b[-1].arrival)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert set(r.prompt_len for r in a) <= set(generator.buckets(mix))
    assert generator.requests(mix, 0.2, 51, 1, 8) == a


def test_longgen_fills_every_slot_first():
    mix = generator.load_mix("longgen")
    reqs = generator.requests(mix, 0.3, 51, 9, 16)
    fill = [r for r in reqs if r.arrival == 0.0]
    assert len(fill) == 16
    assert sorted(r.max_new_tokens for r in fill) == sorted(
        generator.quantiles(mix["output"], 16))
    assert all(384 <= r.max_new_tokens <= 896 for r in reqs)


def finished_engine(slots_and_lengths):
    from types import SimpleNamespace as NS
    fin = [NS(req=NS(rid=i), slot=slot, tokens=[0] * n)
           for i, (slot, n) in enumerate(slots_and_lengths)]
    return NS(sched=NS(finished=fin))


def test_the_check_samples_the_longest_then_distinct_slots():
    # slots 0-2, the longest (rid 3) in slot 1; three more from slot 0
    eng = finished_engine([(0, 5), (0, 6), (2, 4), (1, 9), (0, 7), (0, 3)])
    for seed in (1, 2, 2 ** 40 + 3):
        chosen = bench.sample(eng, seed, want_tokens=1, want_requests=3)
        assert chosen[0].req.rid == 3
        assert sorted(st.slot for st in chosen) == [0, 1, 2]
        # more tokens wanted than three requests hold: more requests
        chosen = bench.sample(eng, seed, want_tokens=25, want_requests=3)
        assert sum(len(st.tokens) for st in chosen) >= 25
        assert sum(len(st.tokens) for st in chosen[:-1]) < 25
        assert len({st.req.rid for st in chosen}) == len(chosen) > 3
    assert bench.sample(finished_engine([]), 1, 1, 1) == []
    every = bench.sample(eng, 5, want_tokens=1000, want_requests=1)
    assert len(every) == 6


@pytest.mark.parametrize("text,want", [
    ("5101", [5101]), ("5101-5103", [5101, 5102, 5103]),
    ("7,5101-5102", [7, 5101, 5102])])
def test_series_reads_seed_lists(text, want):
    from chipbench import series
    assert series.seeds(text) == want


def test_series_spread_is_the_quartile_range_over_the_median():
    from chipbench import series
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 20.0]
    q1, q2, q3 = [10.75, 12.5, 15.5]  # statistics.quantiles, exclusive
    assert series.spread(xs) == pytest.approx((q3 - q1) / q2)
    assert math.isnan(series.spread([1.0]))
    assert math.isnan(series.spread([0.0, 0.0, 0.0]))
