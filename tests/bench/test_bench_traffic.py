"""The traffic generator: deterministic for a seed, the same work on every
seed, and lengths and arrivals as the mix files state."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "bench")))

import spec  # noqa: E402
import traffic as TR  # noqa: E402


#: Arrival rates for mixes whose rate a cell file sets.
RATES = {"chat": 0.48, "sessions": 0.3}


def mix(name):
    p = spec.load_traffic(name)
    if name in RATES:
        p["rate_per_s"] = RATES[name]
    return p


def items(p, seed, window=30.0):
    t = TR.Traffic(p, seed, 64000, window_s=window, n_slots=8)
    return t, [(it.due, len(it.prompt), it.max_new, it.prompt[:4].tolist())
               for it in t.pending]


@pytest.mark.parametrize("name", ["chat", "sessions"])
def test_deterministic_for_a_seed(name):
    p = mix(name)
    assert items(p, 2 ** 33 + 1)[1] == items(p, 2 ** 33 + 1)[1]


@pytest.mark.parametrize("name", ["chat", "sessions"])
def test_every_seed_gets_the_same_schedule(name):
    """The seed draws the tokens; lengths and due times are the mix's."""
    p = mix(name)
    runs = [items(p, seed) for seed in (3, 4, 2 ** 40 + 9)]
    sched = [[(d, n, m) for d, n, m, _ in its] for _, its in runs]
    assert sched[0] == sched[1] == sched[2]
    assert len(sched[0]) > 10
    toks = [[t for *_, t in its] for _, its in runs]
    assert toks[0] != toks[1]


def test_lengths_follow_their_distributions():
    rng = np.random.default_rng(0)
    d = {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32,
         "max": 1792}
    x = TR.draw_lengths(rng, d, 40000)
    assert x.min() >= 32 and x.max() <= 1792
    assert abs(np.median(x) / 384 - 1) < 0.03
    # Log-normal spread: the 84th percentile sits sigma above the median.
    assert abs(np.log(np.percentile(x, 84.13) / 384) - 0.8) < 0.04
    u = TR.draw_lengths(rng, {"dist": "loguniform", "min": 1024,
                              "max": 1536}, 40000)
    assert u.min() >= 1024 and u.max() <= 1536
    assert abs(np.mean(np.log(u)) - (np.log(1024) + np.log(1536)) / 2) < 0.01
    with pytest.raises(ValueError):
        TR.draw_lengths(rng, {"dist": "zipf"}, 3)


def test_poisson_arrivals_at_the_stated_rate():
    g = TR._gaps(np.random.default_rng(1), 2.0, 5000.0)
    assert abs(len(g) / 5000.0 - 2.0) < 0.1
    # Exponential gaps: the standard deviation equals the mean.
    assert abs(g.std() / g.mean() - 1) < 0.05


def test_sessions_share_system_prompts_and_grow_their_history():
    p = mix("sessions")
    t = TR.Traffic(p, 17, 64000, window_s=200.0, n_slots=8)
    firsts = list(t.pending)
    sys_ids = [t.history[it.session]["sys"] for it in firsts]
    share = np.bincount(sys_ids, minlength=8) / len(sys_ids)
    h8 = sum(1 / k for k in range(1, 9))
    assert abs(share[0] - 1 / h8) < 0.08 and share[0] > share[-1]
    it = firsts[0]
    sp = t.system[t.history[it.session]["sys"]]
    assert len(sp) == p["system_len"]
    assert np.array_equal(it.prompt[:len(sp)], sp)
    served = np.arange(it.max_new, dtype=np.int32)
    t.finished(it, served, now=5.0)
    nxt = [x for x in t.pending if x.session == it.session and x.turn]
    assert len(nxt) == 1 and nxt[0].turn == 1 and nxt[0].due >= 5.0
    n = len(it.prompt)
    assert np.array_equal(nxt[0].prompt[:n], it.prompt)
    assert np.array_equal(nxt[0].prompt[n:n + len(served)], served)
    assert t.longest_prompt() + 160 <= 2048


def test_backlog_keeps_the_queue_full():
    p = mix("longdoc")
    t = TR.Traffic(p, 5, 122753, window_s=30.0, n_slots=6)
    got = t.pop_due(0.0, n_queued=2)
    assert len(got) == 4
    assert all(1024 <= len(i.prompt) <= 1536 and 256 <= i.max_new <= 512
               for i in got)
    assert t.pop_due(1.0, n_queued=6) == []
