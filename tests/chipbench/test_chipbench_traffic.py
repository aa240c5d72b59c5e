"""The traffic generator: deterministic per seed, and true to its file."""

import math
import sys
from pathlib import Path
from statistics import NormalDist, median

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import harness  # noqa: E402
from chipbench.traffic import Traffic, exponential_quantiles, length_quantiles  # noqa: E402

SEED = 2**31 + 12345  # larger than 32 signed bits hold


def mix(name):
    m = harness.load_json(harness.mix_file(name))
    if m["loop"] == "open":
        m["rate_per_s"] = 2.0
    return m


def test_open_schedule_is_deterministic_per_seed():
    a = Traffic(mix("chat"), SEED, 45, 1000).open_schedule()
    b = Traffic(mix("chat"), SEED, 45, 1000).open_schedule()
    c = Traffic(mix("chat"), SEED + 1, 45, 1000).open_schedule()
    assert [(d.prompt, d.max_new_tokens, d.due_s) for d in a] == \
        [(d.prompt, d.max_new_tokens, d.due_s) for d in b]
    assert [d.prompt for d in a] != [d.prompt for d in c]


def test_every_seed_gets_the_same_work():
    """The seed draws token ids only: lengths, their pairing and the
    arrival times are the same for every seed."""
    runs = [Traffic(mix("chat"), s, 45, 1000).open_schedule() for s in (1, 2, SEED)]
    work = [[(len(d.prompt), d.max_new_tokens, d.due_s) for d in r] for r in runs]
    assert work[0] == work[1] == work[2]
    assert runs[0][0].prompt != runs[1][0].prompt
    closed = [Traffic(mix("longgen"), s, 45, 1000) for s in (1, SEED)]
    draws = [[t.next_draw() for _ in range(2 * t.pool_size)] for t in closed]
    assert [(len(d.prompt), d.max_new_tokens) for d in draws[0]] == \
        [(len(d.prompt), d.max_new_tokens) for d in draws[1]]


def test_open_loop_sends_the_rate_over_the_window():
    r = Traffic(mix("chat"), SEED, 45, 1000).open_schedule()
    assert len(r) == math.ceil(2.0 * 45)
    # quantile gaps: their sum is the window, to within one mean gap
    assert abs(r[-1].due_s - 45) < 1 / 2.0 + 1.0
    assert all(b.due_s > a.due_s for a, b in zip(r, r[1:]))


@pytest.mark.parametrize("name", ["chat", "longgen", "docs"])
def test_lengths_follow_the_files_distributions(name):
    m = mix(name)
    for key in ("prompt_tokens", "output_tokens"):
        spec = m[key]
        q = length_quantiles(spec, 2001)
        assert min(q) >= spec["min"] and max(q) <= spec["max"]
        if spec["dist"] == "lognormal":
            assert median(q) == pytest.approx(spec["median"], abs=1)
            # the 84th percentile sits one sigma above the median in log space
            at84 = q[round(NormalDist().cdf(1.0) * 2001)]
            want = min(spec["max"], spec["median"] * math.exp(spec["sigma"]))
            assert at84 == pytest.approx(want, rel=0.02)
        else:
            assert median(q) == pytest.approx((spec["min"] + spec["max"]) / 2, abs=1)


def test_exponential_quantiles_have_the_rate_as_mean():
    gaps = exponential_quantiles(4.0, 4000)
    assert sum(gaps) / len(gaps) == pytest.approx(0.25, rel=0.01)


def test_closed_loop_reshuffles_the_same_pool():
    t = Traffic(mix("longgen"), SEED, 45, 1000)
    first = [t.next_draw() for _ in range(t.pool_size)]
    second = [t.next_draw() for _ in range(t.pool_size)]
    key = sorted((len(d.prompt), d.max_new_tokens) for d in first)
    assert sorted(len(d.prompt) for d in first) == sorted(len(d.prompt) for d in second)
    assert [d.index for d in first + second] == list(range(2 * t.pool_size))
    assert key  # lengths within the file's bounds
    spec = mix("longgen")["prompt_tokens"]
    assert all(spec["min"] <= len(d.prompt) <= spec["max"] for d in first)
    assert all(0 <= tok < 1000 for d in first for tok in d.prompt)


def test_every_mix_fits_its_engine():
    for name in ("chat", "longgen", "docs"):
        m = mix(name)
        longest = m["prompt_tokens"]["max"] + m["output_tokens"]["max"]
        assert longest < m["engine"]["max_len"]
