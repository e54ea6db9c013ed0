"""The open-loop schedule and the backlog, from their seeds."""
import collections
import itertools

import numpy as np
import pytest

from bench import traffic

SEED = 2 ** 33 + 17             # seeds may run past 32 bits


def _key(spec):
    return spec.prompt, spec.max_new_tokens


def test_schedule_repeats_from_its_seed():
    mix = traffic.load_mix("decisions")
    a = traffic.Traffic(mix, 50, SEED).specs
    b = traffic.Traffic(mix, 50, SEED).specs
    assert a == b


def test_seeds_share_the_gaps_and_requests_in_another_order():
    """Each seed turns one fixed schedule on a circle of the window's
    length: the same requests at the same circular spacings, started at
    another arrival."""
    mix = traffic.load_mix("decisions")
    a = traffic.Traffic(mix, 50, SEED).specs
    b = traffic.Traffic(mix, 50, SEED + 1).specs
    k = round(mix["arrivals"]["rate_per_s"] * 50)
    assert len(a) == len(b) == k
    assert [s.due_s for s in a] != [s.due_s for s in b]
    fixed = traffic.poisson_conditioned(mix["arrivals"]["rate_per_s"], 50,
                                        mix["pool_seed"])
    drawn = [(r["prompt"], r["reply_bytes"])
             for r in traffic.load_pool(mix)[:k]]

    def circular_gaps(specs):
        t = [x.due_s for x in specs]
        return np.diff(t + [t[0] + 50])

    for s in (a, b):
        assert all(0 <= x.due_s < 50 for x in s)
        assert [x.due_s for x in s] == sorted(x.due_s for x in s)
        # a turn of the fixed schedule: the arrival at the turn's start
        # carries request i, and one shift carries every arrival onto the
        # schedule's own, with the request drawn with it
        i = drawn.index(_key(s[0]))
        turned = s[k - i:] + s[:k - i]
        shift = fixed[i] - s[0].due_s
        assert np.allclose([(x.due_s + shift) % 50 for x in turned], fixed)
        assert [_key(x) for x in turned] == drawn
        assert np.allclose(sorted(circular_gaps(s)), sorted(np.diff(
            fixed + [fixed[0] + 50])))
    assert [_key(x) for x in a] != [_key(x) for x in b]
    assert collections.Counter(map(_key, a)) == \
        collections.Counter(map(_key, b))


@pytest.mark.parametrize("seconds", [50, 5])
def test_schedule_repeats_around_the_window(seconds):
    """The lead-in is the schedule's own end a period early (at most one
    period of it), and past the close the schedule goes on from its start,
    period after period."""
    mix = traffic.load_mix("decisions")
    tr = traffic.Traffic(mix, seconds, SEED)
    lead = min(mix["lead_in_s"], seconds)
    assert tr.lead_in_s == lead > 0
    end = [s for s in tr.specs if s.due_s >= seconds - lead]
    assert tr.lead_in and len(tr.lead_in) == len(end)
    for a, b in zip(tr.lead_in, end):
        assert a.due_s == pytest.approx(b.due_s - seconds)
        assert _key(a) == _key(b) and a.idx == b.idx
    assert all(-lead <= s.due_s < 0 for s in tr.lead_in)
    k = len(tr.specs)
    after = list(itertools.islice(tr.after(), 2 * k))
    for j, s in enumerate(after):
        base = tr.specs[j % k]
        assert s.due_s == pytest.approx(base.due_s + (1 + j // k) * seconds)
        assert _key(s) == _key(base)


def test_a_closed_loop_has_no_lead_in():
    tr = traffic.Traffic(traffic.load_mix("cot-backlog"), 50, SEED)
    assert tr.lead_in_s == 0 and tr.lead_in == []
    assert list(tr.after()) == []


def test_decision_prompts_fill_one_prefill_bucket():
    """Every prompt is over 1024 tokens with its BOS, so the 2048 bucket is
    the only prefill shape the decision mixes use; replies are the twin's."""
    rows = traffic.load_pool(traffic.load_mix("decisions"))
    assert min(len(r["prompt"].encode()) for r in rows) + 1 > 1024
    assert {r["kind"] for r in rows} == {
        "read", "update", "admission", "replication", "recovery",
        "coherence", "plan_cache"}
    assert all(90 <= r["reply_bytes"] <= 180 for r in rows)


def test_backlog_cycles_fixed_lengths():
    mix = traffic.load_mix("cot-backlog")
    t = traffic.Traffic(mix, 50, SEED)
    assert not t.open_loop and t.specs == []
    it = t.backlog()
    n = len(traffic.load_pool(mix))
    first = [next(it) for _ in range(2 * n)]
    lens = sorted(s.max_new_tokens for s in first[:n])
    assert lens[0] == 256 and lens[-1] == 512
    assert [_key(s) for s in first[:n]] == [_key(s) for s in first[n:2 * n]]
    other = traffic.Traffic(mix, 50, SEED + 1).backlog()
    second = [next(other) for _ in range(n)]
    assert sorted(map(_key, second)) == sorted(map(_key, first[:n]))


def test_mmpp_is_seeded():
    a = traffic.mmpp(0.5, 2.0, 60, 5, 5, SEED)
    assert a == traffic.mmpp(0.5, 2.0, 60, 5, 5, SEED)
    assert a != traffic.mmpp(0.5, 2.0, 60, 5, 5, SEED + 1)
    assert all(0 <= t < 60 for t in a) and a == sorted(a)


def test_bad_rates_fail_fast():
    with pytest.raises(ValueError):
        traffic.poisson_conditioned(0.0, 50, 1)
