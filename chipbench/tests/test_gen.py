import numpy as np

from chipbench import gen
from chipbench.tests.smoke import TRAFFIC


def test_seeds_replay_one_schedule_with_other_tokens():
    a = gen.serve_requests(TRAFFIC, 3, 10.0, 256)
    b = gen.serve_requests(TRAFFIC, 2**33 + 5, 10.0, 256)
    assert len(a) == len(b) > TRAFFIC["backlog"]
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = gen.serve_requests(dict(TRAFFIC, schedule_seed=1), 3, 10.0, 256)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a]


def test_backlog_then_arrivals_at_the_stated_rate():
    """The backlog is due when the window opens; the arrivals follow at the
    Poisson gaps of the stated rate, none moved to fit the window."""
    seconds = 50.0
    reqs = gen.serve_requests(TRAFFIC, 3, seconds, 256)
    due = np.array([r.due_s for r in reqs])
    n_back = TRAFFIC["backlog"]
    assert np.all(due[:n_back] == 0) and np.all(due[n_back:] > 0)
    assert np.all(np.diff(due) >= 0) and due[-1] < seconds
    gaps = np.diff(due[n_back - 1:])
    rate = TRAFFIC["rate_per_s"]
    assert abs(np.mean(gaps) * rate - 1) < 0.05
    full = gen.gaps("poisson", rate, int(np.ceil(rate * seconds)))
    assert np.all(np.isin(np.round(gaps, 9), np.round(full, 9)))


def test_same_seed_same_requests():
    a = gen.serve_requests(TRAFFIC, 7, 5.0, 256)
    b = gen.serve_requests(TRAFFIC, 7, 5.0, 256)
    assert all(x.due_s == y.due_s and x.max_new == y.max_new
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_follow_the_distribution():
    x = gen.lengths({"dist": "log_uniform", "min": 1024, "max": 32768}, 400)
    assert x.min() >= 1024 and x.max() <= 32768
    assert abs(np.mean(np.log(x)) - np.log(1024 * 32768) / 2) < 0.01
    u = gen.lengths({"dist": "uniform", "min": 64, "max": 256}, 193)
    assert sorted(u) == list(range(64, 257))
