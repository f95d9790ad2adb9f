"""The seeded traffic generator: same seed, same schedule; every length on
the mix's grid; the rate and the tag shares as the mix states; each seed
its own sizes, drawn from the same slices of each distribution."""
import math
from statistics import NormalDist

import numpy as np
import pytest

from bench import traffic

SLOTS = 128


@pytest.fixture(params=["azure-conv", "azure-conv-closed"])
def mix(request):
    return traffic.load_mix(request.param)


def test_grids_hold_the_issue_lengths():
    assert traffic.length_grid(traffic.load_mix("azure-conv")["prompt"]) == \
        list(range(128, 1665, 128))


def test_mix_names_its_public_source(mix):
    assert "AzurePublicDataset" in mix["source"]


def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, 2**33 + 17, 30, slots=SLOTS, vocab_size=1000)
    b = traffic.schedule(mix, 2**33 + 17, 30, slots=SLOTS, vocab_size=1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.due, x.prompt_len, x.max_new_tokens, x.tag) == \
            (y.due, y.prompt_len, y.max_new_tokens, y.tag)
        np.testing.assert_array_equal(x.tokens, y.tokens)


def test_seeds_draw_their_own_sizes_from_the_same_slices(mix):
    a = traffic.schedule(mix, 1, 30, slots=SLOTS, vocab_size=1000)
    b = traffic.schedule(mix, 2, 30, slots=SLOTS, vocab_size=1000)
    assert len(a) == len(b)
    assert [s.prompt_len for s in a] != [s.prompt_len for s in b]
    assert sorted(s.max_new_tokens for s in a) != sorted(s.max_new_tokens for s in b)
    # Both fill the same span (the last gap differs).
    assert max(s.due for s in a) == pytest.approx(max(s.due for s in b), abs=1.0)
    # Each block's k-th smallest size lies in the k-th slice of the
    # distribution for every seed, so the blocks' totals stay close.
    block = traffic.BLOCK
    for key, dist in ((lambda s: s.prompt_len, mix["prompt"]),
                      (lambda s: s.max_new_tokens, mix["output"])):
        edges = np.exp(math.log(dist["median"]) + dist["sigma"] * np.array(
            [NormalDist().inv_cdf(k / block) for k in range(1, block)]))
        for start in range(0, len(a) - block + 1, block):
            for run in (a, b):
                got = sorted(key(s) for s in run[start:start + block])
                for k in range(block - 1):
                    # Rounding up and clipping move a size past its edge by
                    # at most one quantum, or onto the clip.
                    assert got[k] <= max(edges[k] + dist.get("quantum", 1),
                                         dist["min"] + dist.get("quantum", 1))
                    assert got[k + 1] >= min(edges[k] - 1, dist["max"])


def test_lengths_on_the_grid_and_in_range(mix):
    grid = set(traffic.length_grid(mix["prompt"]))
    out = mix["output"]
    for spec in traffic.schedule(mix, 5, 30, slots=SLOTS, vocab_size=1000):
        assert spec.prompt_len in grid
        assert len(spec.tokens) == spec.prompt_len
        assert spec.tokens.min() >= 1 and spec.tokens.max() < 1000
        assert out["min"] <= spec.max_new_tokens <= out["max"]


def test_tag_shares_are_exact(mix):
    specs = traffic.schedule(mix, 9, 30, slots=SLOTS, vocab_size=1000)
    for tag, share in mix["tags"].items():
        want = None if tag == "untagged" else tag
        got = sum(1 for s in specs if s.tag == want)
        assert abs(got - share * len(specs)) <= 1


def test_tags_mix_along_the_run(mix):
    """Each quarter of the run sends every tag near its share."""
    if mix["loop"] == "open":
        mix = dict(mix, rate_rps=20.0)      # enough requests for shares to show
    specs = traffic.schedule(mix, 4, 30, slots=SLOTS, vocab_size=1000)
    quarter = len(specs) // 4
    for q in range(4):
        part = specs[q * quarter:(q + 1) * quarter]
        for tag, share in mix["tags"].items():
            want = None if tag == "untagged" else tag
            got = sum(1 for s in part if s.tag == want) / len(part)
            assert abs(got - share) < 0.15


def test_open_loop_rate_within_sampling_error():
    mix = dict(traffic.load_mix("azure-conv"), rate_rps=40.0)
    seconds = 60.0
    specs = traffic.schedule(mix, 3, seconds, slots=SLOTS, vocab_size=1000)
    span = seconds + mix["ramp_s"]
    n = len(specs)
    assert n == round(40.0 * span)
    dues = np.array([s.due for s in specs])
    assert np.all(np.diff(dues) > 0) and 0 < dues[0] and dues[-1] < span
    half = np.sum(dues < span / 2)
    # A Poisson count over half the span: mean n/2, sd sqrt(n/2).
    assert abs(half - n / 2) < 4 * math.sqrt(n / 2)
    gaps = np.diff(dues)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_closed_loop_sends_on_completion():
    mix = traffic.load_mix("azure-conv-closed")
    specs = traffic.schedule(mix, 3, 30, slots=SLOTS, vocab_size=1000)
    assert len(specs) == SLOTS * mix["clients_per_slot"] * mix["requests_per_client"]
    assert all(s.due == 0.0 for s in specs)


def test_log_normal_median_and_clip():
    prompt = {"median": 256, "sigma": 0.9, "min": 64, "max": 1536, "quantum": 128}
    lengths = traffic.prompt_lengths(np.random.default_rng(0).random(20000), prompt)
    assert set(lengths) <= set(traffic.length_grid(prompt))
    # Rounding up to 128 moves the median from 256 to the next grid step.
    assert np.median(lengths) in (256, 384)


def test_percentile_is_nearest_rank_over_all_values():
    values = list(range(1, 101))
    assert traffic.percentile(values, 95) == 95
    assert traffic.percentile(values, 50) == 50
    assert traffic.percentile([3.0], 95) == 3.0
    assert traffic.percentile([1.0, float("inf")], 95) == float("inf")
