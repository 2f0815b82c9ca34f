"""Traffic generation and the request-timing arithmetic."""
import math

import numpy as np

from bench import traffic

MIX = traffic.load_mix("serve-mixed")


def test_same_seed_same_inputs():
    assert traffic.arrivals(MIX, 51) == traffic.arrivals(MIX, 51)
    assert traffic.prompt_tokens(4_000_000_017, 3, 300, 151936) == \
        traffic.prompt_tokens(4_000_000_017, 3, 300, 151936)


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    a = traffic.arrivals(MIX, 51)
    assert len(a) == round(MIX["arrivals"]["rate_per_s"] * 51)
    assert 0 < a[0].due_s and max(x.due_s for x in a) < 51
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    assert traffic.prompt_tokens(1, 0, 50, 151936) != \
        traffic.prompt_tokens(2, 0, 50, 151936)


def test_remainders_come_only_from_the_set():
    p = MIX["prompt"]
    sizes = traffic.request_sizes(MIX, 2000)
    rems = {s % p["block"] for s, _ in sizes}
    assert rems == set(p["remainders"])
    assert all(p["block"] <= s <= p["max"] + max(p["remainders"])
               for s, _ in sizes)
    assert all(MIX["output"]["min"] <= o <= MIX["output"]["max"]
               for _, o in sizes)


def test_prompt_tokens_avoid_special_ids():
    toks = traffic.prompt_tokens(9, 0, 5000, 151936)
    assert min(toks) >= traffic.RESERVED and max(toks) < 151936


def test_mlm_batch_is_seeded_and_masked():
    mix = traffic.load_mix("train-mlm")
    a = traffic.batch(mix, 5, 0, 50265)
    b = traffic.batch(mix, 5, 0, 50265)
    c = traffic.batch(mix, 5, 1, 50265)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["tokens"] == c["tokens"]).all()
    share = a["loss_mask"].mean()
    assert 0.13 < share < 0.17
    assert (a["loss_mask"][:, 0] == 0).all()
    assert (a["tokens"][a["loss_mask"] == 0] == a["labels"][
        a["loss_mask"] == 0]).all()


def _timings(firsts, due=0.0, n=10, gap=0.01):
    out = []
    for f in firsts:
        t = traffic.RequestTiming(due)
        if f is not None:
            for i in range(n):
                t.token(f + i * gap)
            t.done = True
        out.append(t)
    return out


def test_missing_first_token_counts_as_infinite():
    ts = _timings([0.1] * 9 + [None])
    assert ts[-1].ttft == math.inf and ts[-1].tpot == math.inf
    assert traffic.percentile([t.ttft for t in ts], 90) == math.inf
    assert traffic.percentile([t.ttft for t in ts], 50) == 0.1


def test_tpot_is_mean_gap_after_the_first_token():
    t = _timings([0.5], n=5, gap=0.02)[0]
    assert math.isclose(t.tpot, 0.02)
    assert math.isclose(t.ttft, 0.5)


def test_a_stall_raises_ttft_p90():
    calm = _timings([0.2] * 20)
    stalled = _timings([0.2] * 17 + [3.0] * 3)
    p_calm = traffic.percentile([t.ttft for t in calm], 90)
    p_stall = traffic.percentile([t.ttft for t in stalled], 90)
    assert p_calm == 0.2 and p_stall > 2.0


def test_percentile_interpolates_like_numpy():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (0, 50, 90, 100):
        assert math.isclose(traffic.percentile(xs, q),
                            float(np.percentile(xs, q)))
