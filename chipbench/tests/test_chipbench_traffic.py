"""Traffic generation and the drivers' clocks, on the CPU."""
import json
import statistics
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import drivers, report, traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
NAMES = sorted(p.stem for p in MIXES.glob("*.json"))
BIG_SEED = 2**31 + 12345
VOCAB = 92544


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    a = traffic.Traffic(mix(name), BIG_SEED, VOCAB).take(100)
    b = traffic.Traffic(mix(name), BIG_SEED, VOCAB).take(100)
    c = traffic.Traffic(mix(name), BIG_SEED + 1, VOCAB).take(100)
    key = lambda s: (s.uid, s.prompt.tobytes(), s.max_new, s.due)  # noqa
    assert [key(s) for s in a] == [key(s) for s in b]
    assert [key(s) for s in a] != [key(s) for s in c]


@pytest.mark.parametrize("name", NAMES)
def test_lengths_inside_clips_and_buckets(name):
    """Lengths keep to their clips, or to their one value; no prompt passes
    the largest prompt bucket (512), so none is truncated."""
    m = mix(name)
    specs = traffic.Traffic(m, BIG_SEED, VOCAB).take(4 * m["stratum"])
    for key, got in (("prompt", [len(s.prompt) for s in specs]),
                     ("output", [s.max_new for s in specs])):
        d = m[key]
        lo, hi = ((d["value"], d["value"]) if d["dist"] == "fixed"
                  else (d["min"], d["max"]))
        assert lo <= min(got) and max(got) <= hi
    assert max(len(s.prompt) for s in specs) <= 512
    assert all((s.prompt >= 0).all() and (s.prompt < VOCAB).all()
               for s in specs)


@pytest.mark.parametrize("name", NAMES)
def test_every_seed_gets_the_same_work(name):
    """Every seed sends the same lengths in the same order, and each block
    of `stratum` requests holds the same lengths as every other block: the
    seed picks the prompt tokens only."""
    m = mix(name)
    k = m["stratum"]
    work = [[(len(s.prompt), s.max_new, s.due)
             for s in traffic.Traffic(m, seed, VOCAB).take(2 * k)]
            for seed in (1, 2, BIG_SEED)]
    assert work[0] == work[1] == work[2]
    blocks = [(Counter(w[0] for w in blk), Counter(w[1] for w in blk))
              for blk in (work[0][:k], work[0][k:])]
    assert blocks[0] == blocks[1]
    assert len({w[:2] for w in work[0]}) > 1       # lengths still vary


def test_arrivals_are_one_trace_for_every_seed():
    m = mix("chat_bursty")
    dues = [[s.due for s in traffic.Traffic(m, seed, VOCAB).take(300)]
            for seed in (1, 2, BIG_SEED)]
    assert dues[0] == dues[1] == dues[2]
    assert len(set(np.round(np.diff(dues[0]), 9))) > 100


def test_lognormal_medians():
    m = mix("decode_batch")
    assert np.median(traffic.lengths(m["prompt"], 1024)) == 128
    assert abs(np.median(traffic.lengths(m["output"], 1024)) - 256) <= 1


def test_gamma_gaps_have_the_stated_variation():
    m = mix("chat_bursty")
    gaps = traffic.quantiles(m["gap"], 4096)
    cv = statistics.pstdev(gaps) / statistics.fmean(gaps)
    assert abs(statistics.fmean(gaps) - 1.0) < 0.01
    assert abs(cv - m["gap"]["cv"]) < 0.15
    specs = traffic.Traffic(m, BIG_SEED, VOCAB).take(m["stratum"])
    rate = len(specs) / specs[-1].due
    assert rate == pytest.approx(m["rate_rps"], rel=0.01)


def test_requests_come_in_order():
    t = traffic.Traffic(mix("decode_batch"), 7, VOCAB)
    t.spec(0)
    with pytest.raises(ValueError):
        t.spec(5)


class FakeRequest:
    def __init__(self, spec):
        self.uid, self.output, self.status = spec.uid, [], "pending"
        self.max_new = spec.max_new


class FakeEngine:
    """Holds `slots` lanes; each step admits from the queue, emits one token
    per lane and sleeps `step_s` (`stall_s` on the steps in `stall_at`)."""

    def __init__(self, slots=4, step_s=0.002, stall_at=(), stall_s=0.0):
        self.queue, self.lanes, self.n, self.slots = [], [], 0, slots
        self.step_s, self.stall_at, self.stall_s = step_s, stall_at, stall_s
        self.queued_at_step = []

    def submit(self, req):
        self.queue.append(req)

    def queue_depth(self):
        return len(self.queue)

    def busy_slots(self):
        return len(self.lanes)

    def step(self):
        self.queued_at_step.append(len(self.queue))
        while self.queue and len(self.lanes) < self.slots:
            self.lanes.append(self.queue.pop(0))
        time.sleep(self.stall_s if self.n in self.stall_at else self.step_s)
        self.n += 1
        for r in self.lanes:
            r.output.append(1)
            if len(r.output) >= r.max_new:
                r.status = "ok"
        self.lanes = [r for r in self.lanes if r.status == "pending"]


def chat_mix(rate):
    return {"driver": "open_loop", "rate_rps": rate,
            "gap": {"dist": "gamma", "cv": 1e-3}, "stratum": 4,
            "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.1,
                       "min": 16, "max": 16},
            "output": {"dist": "lognormal", "median": 4, "sigma": 0.1,
                       "min": 4, "max": 4}}


def ttfts(stall_s):
    eng = FakeEngine(slots=64, stall_at=(0,), stall_s=stall_s)
    drv = drivers.Driver(eng, traffic.Traffic(chat_mix(50.0), 3, 100),
                         FakeRequest)
    window = drivers.run_open_loop(drv, 0.4, drain_s=2.0)
    return [s.first - s.due for s in drv.everyone()
            if s.due < window[1]], drv


def test_stalled_step_shows_in_ttft():
    """Requests due while one step stalls wait for it: their time to first
    token is counted from when they were due, not from when the loop got
    round to sending them, and the lateness records how late that was."""
    calm, _ = ttfts(0.0)
    stalled, drv = ttfts(0.25)
    assert max(calm) < 0.1
    assert max(stalled) > 0.2
    assert max(drv.lateness) > 0.15
    late = [s for s in drv.everyone() if s.sent - s.due > 0.1]
    assert late and all(s.first - s.due >= s.sent - s.due for s in late)


def test_backlog_keeps_the_queue_full_and_counts_the_window():
    eng = FakeEngine()
    m = dict(chat_mix(1.0), driver="backlog", queue_depth=1)
    drv = drivers.Driver(eng, traffic.Traffic(m, 5, 100), FakeRequest)
    t0, t1 = drivers.run_backlog(drv, 0.2, depth=3)
    assert t1 - t0 >= 0.2
    assert min(eng.queued_at_step) >= 3    # refilled before every step
    ctx = report.Ctx(cfg={}, mix=m, peaks={}, setup_s=1.0, window=(t0, t1),
                     seen=drv.everyone(), steps=drv.steps)
    emitted = sum(len(s.req.output) for s in drv.everyone())
    assert sum(r.lanes for r in drv.steps) == emitted
    assert ctx.window_s == t1 - t0 and emitted > 0
