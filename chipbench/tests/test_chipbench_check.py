"""The check that decides `correct`, at test size on the CPU.

A whole run (run.main with the chip's look skipped) of the tiny cells must
come out correct; the same run with the timed path broken underneath must
come out not correct; and the control (the reference in int8) must differ
from the reference.
"""
import json

import numpy as np
import pytest

from chipbench import reference, run
from chipbench.tests import tiny
from chipbench.weights import make_weights


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny checkout; JAX's cache settings are put back afterwards."""
    with tiny.cache_settings_kept():
        yield tiny.make_root(tmp_path_factory.mktemp("tiny"))


def run_cell(root, capsys, cell, seed=2**31 + 5):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                   "--trace", "0"], require_chip=False, root=root)
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(line)
    assert list(result)[-1] == "check"
    return result


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(root, capsys, cell):
    result = run_cell(root, capsys, cell)
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]


def alter_tokens(monkeypatch):
    """Fault: every third decode step's tokens come out altered."""
    from repro.serving.scheduler import ServingEngine
    commit = ServingEngine.commit_step

    def altered(self, plan, next_tok, seconds):
        if self.steps % 3 == 0:
            next_tok = (np.asarray(next_tok) + 1) % self.cfg.vocab
        return commit(self, plan, next_tok, seconds)

    monkeypatch.setattr(ServingEngine, "commit_step", altered)


def invert_selection(monkeypatch):
    """Fault (DSG): each lane keeps its lowest-scoring groups.  The logit
    gaps are read under the run's own selections, so `selection_miss` is
    the number that catches it."""
    from repro.serving.dsg_runtime import DSGRuntime
    write = DSGRuntime._write_rows

    def inverted(self, lane, scores, seed_ema):
        return write(self, lane, -np.asarray(scores), seed_ema)

    monkeypatch.setattr(DSGRuntime, "_write_rows", inverted)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.tiny_batch", alter_tokens),
    ("tiny.tiny_chat", alter_tokens),
    ("tiny-dsg.tiny_batch", alter_tokens),
    ("tiny-dsg.tiny_batch", invert_selection),
])
def test_broken_timed_path_is_not_correct(root, capsys, monkeypatch, cell,
                                          fault):
    fault(monkeypatch)
    result = run_cell(root, capsys, cell)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["check"].values())


def test_control_is_not_the_reference():
    """The control (the reference with int8 weight products) puts another
    token first at some positions, where the reference itself reads no gap.
    At this size int8 and bf16 rounding are alike; the control is held
    against the limit on the chip, at the cells' own size (PERF.md)."""
    cfg = json.loads((tiny.DATA / "tiny.json").read_text())
    w = make_weights(cfg, 7)
    rng = np.random.default_rng(7)
    v = cfg["vocab_size"]
    rows = [(rng.integers(0, v, 32).astype(np.int32),
             rng.integers(0, v, 64).tolist()) for _ in range(4)]
    got = reference.compare(cfg, w, rows, n_rows=4, control=True)
    assert got["tokens"] == 4 * 64
    assert got["control"]["not_first"] > 0
    assert got["control"]["mean_gap"] > 0


def test_selection_miss_by_hand():
    """A top-k reads at most 0; a selection that drops a score above one it
    keeps reads their difference over the keep-th highest score."""
    scores = np.array([[4.0, 3.0, 2.0, 1.0]])
    top = np.array([[True, True, False, False]])
    assert reference.selection_miss(scores, top, keep=2) == pytest.approx(
        (2.0 - 3.0) / 3.0)
    swapped = np.array([[True, False, True, False]])
    assert reference.selection_miss(scores, swapped, keep=2) == \
        pytest.approx((3.0 - 2.0) / 3.0)


def test_given_selections_hold_until_the_next():
    cfg = json.loads((tiny.DATA / "tiny-dsg.json").read_text())
    groups = cfg["intermediate_size"] // cfg["dsg"]["block"]
    layers = cfg["num_hidden_layers"]
    a = np.zeros((layers, groups), bool)
    a[:, 0] = True
    b = np.zeros((layers, groups), bool)
    b[:, 1] = True
    given, use = reference.given_selections(
        cfg, [[(4, 5, b), (3, 3, a)], []], n_rows=2, t_len=10)
    assert given.shape == (layers, 2, 10, groups)
    assert use[0].tolist() == [False] * 3 + [True] * 7
    assert not use[1].any()
    assert given[:, 0, 3:5, 0].all() and not given[:, 0, 3:5, 1].any()
    assert given[:, 0, 5:, 1].all() and not given[:, 0, 5:, 0].any()


def test_selections_are_logged_per_request(root):
    """A DSG run's driver files every pattern the runtime writes under its
    request: the admission's first, from the last prompt token, then one at
    each refresh, `refresh_interval` tokens apart."""
    from chipbench import bench, drivers, traffic
    from repro.serving.scheduler import Request
    cell = bench.resolve("tiny-dsg.tiny_batch", root)
    cfg, mix = cell.config, cell.traffic
    w = make_weights(cfg, 3)
    eng = run.build_engine(cfg, w, 3)
    gen = traffic.Traffic(mix, 3, cfg["vocab_size"])

    def make_request(spec):
        return Request(uid=spec.uid, prompt=spec.prompt, max_new=spec.max_new)

    drv = drivers.Driver(eng, gen, make_request)
    drv.selections = drivers.SelectionLog(eng)
    for spec in gen.take(2):
        drv.submit(spec, 0.0)
    while drv.busy():
        drv.step()
    refresh = cfg["dsg"]["refresh_interval"]
    keep = reference.dsg_keep(cfg)
    for s in drv.done:
        sel = drv.selections.by_uid[s.spec.uid]
        p, n = len(s.spec.prompt), len(s.req.output)
        assert [e[0] for e in sel] == [p - 1] + [
            p - 1 + refresh * j for j in range(1, (n - 1) // refresh + 1)]
        assert [e[1] for e in sel] == [p - 1] + [e[0] + 1 for e in sel[1:]]
        assert all((e[2].sum(-1) == keep).all() for e in sel)


def test_calibrate_puts_both_through_the_verdict(root, capsys):
    """calibrate.py reports, for the program and for the control, the
    `correct` that a run's decision gives against the configuration's
    limits.  (At this size int8 rounds like bf16, so the control is not
    expected to fail here: its chip readings are held in the next test.)"""
    from chipbench import calibrate
    assert calibrate.main(["--workload", "tiny-dsg.tiny_batch", "--seconds",
                           "1", "--seeds", "11"], require_chip=False,
                          root=root) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    rec, summary = lines[-2], lines[-1]
    assert rec["program_correct"] is True
    assert isinstance(rec["control_correct"], bool)
    assert "selection_miss" in rec["program"] and "selection_miss" in \
        rec["control"]
    assert summary["program_correct"] == 1


READINGS = tiny.DATA / "limit_readings.jsonl"


def recorded():
    """calibrate.py's per-seed lines from the chip, as committed."""
    return [json.loads(x) for x in READINGS.read_text().splitlines()
            if x.strip()]


@pytest.mark.parametrize("side,expect", [("program", True),
                                         ("control", False)])
def test_chip_readings_against_the_limits(side, expect):
    """The readings that the limits were set from, put through the decision
    a run makes, against each configuration as committed: every sound run
    of the program is correct and the control (the reference in int8, in
    the program's place) is not, on every seed."""
    from chipbench import bench
    files = {c["name"]: c["file"] for c in bench.load()["configs"]}
    rows = [(np.zeros(1, np.int32), [0])]
    seen = set()
    for rec in recorded():
        name = rec["workload"].rsplit(".", 1)[0]
        cfg = json.loads((tiny.REPO / files[name]).read_text())
        ok, check = reference.verdict(cfg, rows, rec[side])
        assert ok is expect, (rec["workload"], rec["seed"], check)
        seen.add(name)
    assert seen == set(files)



def finished(uids, lengths):
    """A driver's finished requests, in the order given; each prompt holds
    its uid."""
    from types import SimpleNamespace as NS
    return NS(selections=None, done=[
        NS(spec=NS(uid=u, prompt=np.array([u], np.int32)),
           req=NS(status="ok", output=[1] * lengths[u])) for u in uids])


def test_sample_is_drawn_from_the_seed_alone():
    """Two runs of one seed that finished the same requests in another
    order check the same requests, and one that finished a request more
    checks them too, or that one among them; the longest among equals is
    the first by uid."""
    lengths = [5, 9, 3, 9, 7, 2, 6, 8, 4, 1] * 4 + [2]
    check = {"max_requests": 6, "min_served_tokens": 1000}
    order = np.random.default_rng(0).permutation(40).tolist()

    def sampled(uids, seed):
        rows, sels = run.sample_rows(finished(uids, lengths), check, seed)
        assert sels is None
        return [int(prompt[0]) for prompt, _ in rows]

    seed = 2**31 + 77
    first = sampled(range(40), seed)
    assert first[0] == 1 and len(first) == 6     # 1, 3, 11, ... are longest
    assert sampled(order, seed) == first
    more = [u for u in sampled(order + [40], seed) if u != 40]
    assert more == first[:len(more)]
    assert sampled(range(40), seed + 1) != first
