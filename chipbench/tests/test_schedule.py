"""Every seed's schedule has the same length and gap multisets, in
another order (a phase of one fixed cycle), at exactly the stated rate,
whatever the window's length; TTFT and TBT are timed from the due
time."""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import schedule  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "mixes").glob("*.json"))
SECONDS = 51                  # the period of every mix's cycle


def _window(reqs):
    w = [r for r in reqs if r.in_window]
    dues = np.asarray([r.due for r in w])
    gaps = np.diff(np.concatenate([dues, [SECONDS]]))
    return (np.asarray([len(r.prompt) for r in w]),
            np.asarray([r.max_new for r in w]), gaps)


def _phases(mix, n_seeds):
    """Seeds that enter the cycle at different phases."""
    n = len(schedule.cycle(mix)[0])
    seeds, seen = [], set()
    for s in [1, 2**31 + 11, 2**33 + 5] + list(range(100, 200)):
        k = int(np.random.default_rng(s).integers(n))
        if k not in seen:
            seen.add(k)
            seeds.append(s)
        if len(seeds) == n_seeds:
            break
    return seeds


@pytest.mark.parametrize("mix", MIXES)
def test_same_multisets_other_order(mix):
    m = json.loads((HERE / "mixes" / f"{mix}.json").read_text())
    seeds = _phases(m, 4)
    runs = [_window(schedule.build(m, SECONDS, s, 1000)) for s in seeds]
    p0, o0, g0 = runs[0]
    for p, o, g in runs[1:]:
        assert sorted(p) == sorted(p0) and sorted(o) == sorted(o0)
        np.testing.assert_allclose(np.sort(g), np.sort(g0), rtol=1e-9,
                                   atol=1e-9)
    for i in range(3):                                # every order differs
        assert len({tuple(np.round(r[i], 9)) for r in runs}) == len(seeds)
    # the orders are phases of one cycle
    p_cycle = list(schedule.cycle(m)[0])
    for p, _, _ in runs:
        k = p_cycle.index(p[0])
        assert list(p) == p_cycle[k:] + p_cycle[:k] or len(set(p_cycle)) < len(p_cycle)


@pytest.mark.parametrize("mix", MIXES)
def test_exact_rate(mix):
    m = json.loads((HERE / "mixes" / f"{mix}.json").read_text())
    n, period = m["cycle"]["requests"], m["cycle"]["period_s"]
    assert period == SECONDS
    reqs = schedule.build(m, SECONDS, 7, 1000)
    win = [r.due for r in reqs if r.in_window]
    lead = [r.due for r in reqs if not r.in_window]
    assert len(win) == n                              # exactly, at 51 s
    assert win[0] == 0.0 and max(win) < SECONDS
    g = schedule.cycle(m)[2]
    assert g.sum() == pytest.approx(period, rel=1e-12)  # one period
    assert lead and -m["lead_in_s"] <= min(lead) and max(lead) < 0


@pytest.mark.parametrize("mix", MIXES)
def test_cycle_does_not_depend_on_the_window(mix):
    """A shorter window holds the first requests of the same cycle from
    the same phase, a longer one the cycle again; the lead-in is the
    same."""
    m = json.loads((HERE / "mixes" / f"{mix}.json").read_text())
    n, period = m["cycle"]["requests"], m["cycle"]["period_s"]
    seed = 2**31 + 5
    full = schedule.build(m, period, seed, 1000)
    key = lambda r: (round(r.due, 9), len(r.prompt), r.max_new)
    for seconds in (12, period / 2, 2 * period + 3):
        part = schedule.build(m, seconds, seed, 1000)
        win = [key(r) for r in part if r.in_window]
        assert [key(r) for r in part if not r.in_window] == \
            [key(r) for r in full if not r.in_window]
        assert all(d < seconds for d, _, _ in win)
        one = [key(r) for r in full if r.in_window]
        again = [(round(d + period * k, 9), p, o) for k in range(3)
                 for d, p, o in one]
        assert win == [x for x in again if x[0] < seconds]
        if seconds > period:
            assert len(win) > 2 * n


def test_same_seed_same_schedule():
    m = json.loads((HERE / "mixes" / f"{MIXES[0]}.json").read_text())
    a = schedule.build(m, 20, 5, 1000)
    b = schedule.build(m, 20, 5, 1000)
    assert all(x.due == y.due and np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))
    c = schedule.build(m, 20, 6, 1000)
    assert not np.array_equal(a[0].prompt, c[0].prompt)


class FakeEngine:
    """Admits everything, takes ``step_s`` per step, gives each sequence
    its first token on its first step and one token per step after."""

    def __init__(self, step_s):
        self.step_s, self.waiting, self.active, self.n = step_s, [], [], 0

    def submit(self, prompt, max_new):
        seq = SimpleNamespace(tokens=list(prompt), prompt_len=len(prompt),
                              max_new=max_new, done=False)
        self.waiting.append(seq)
        self.n += 1
        return self.n - 1

    def step(self):
        self.active += self.waiting
        self.waiting = []
        if not self.active:
            return False
        time.sleep(self.step_s)
        for s in self.active:
            s.tokens.append(1)
            s.done = len(s.tokens) - s.prompt_len >= s.max_new
        self.active = [s for s in self.active if not s.done]
        return True


def test_ttft_and_tbt_from_due_time():
    import jax

    import run
    from metrics import tbt_p95_ms, ttft_p50_s
    step = 0.05
    reqs = [schedule.Request(i, 0.01 * i, np.ones(4, np.int32), 3, True)
            for i in range(5)]
    rec = SimpleNamespace(requests=[dict(idx=q.idx, due=q.due, in_window=True,
                                         seq=None, submit=None, first=None,
                                         stamps=[], output=None)
                                    for q in reqs],
                          steps=[], trace_host=None, trace_s=0, loop_s=0)
    base = time.perf_counter() + 0.02
    run.drive(FakeEngine(step), jax, reqs, base, base + 0.5, 5.0, rec, None)
    win = rec.requests
    late = [r["submit"] - (base + r["due"]) for r in win]
    assert max(late) > 0.01          # the step held the generator back
    runrec = SimpleNamespace(t0=base, t1=base + 0.5, window=win,
                             requests=win, np=np)
    ttft = [r["first"] - (base + r["due"]) for r in win]
    assert ttft_p50_s.read(runrec) == pytest.approx(float(np.median(ttft)))
    # measured from the due time, TTFT includes the generator's lateness
    assert all(r["first"] - (base + r["due"]) >= r["first"] - r["submit"]
               for r in win)
    assert all(t >= step * 0.9 for t in ttft)
    gaps = np.concatenate([np.diff(r["stamps"]) for r in win])
    assert tbt_p95_ms.read(runrec) == pytest.approx(
        float(np.percentile(gaps, 95)) * 1e3)
    assert len(win[0]["stamps"]) == 3
