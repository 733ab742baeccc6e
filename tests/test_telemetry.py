"""Spans and counters of the serving engine (serving/telemetry.py).

Each engine records, for every step, one span per host phase: the step,
its begin half (admissions, page-table growth), the decode dispatch, the
wait for its tokens and the commit; plus one span per admission and per
retirement.  These tests pin that tree, its nesting and its attributes on
a dense, a DSG and a fused-chunk engine, the bounded ring, the registry
and the recorders of engines under the threaded executor.
"""
import gc

import pytest

from harness import (engine_spec, make_engine_parts, mixed_traffic,
                     run_and_collect)
from repro.serving import telemetry
from repro.serving.dsg_runtime import DSGServingConfig

PAGED = {"cache_backend": "paged", "page_size": 8, "cache_tokens": 160}
ENGINES = {
    "dense": PAGED,
    "dsg": dict(PAGED, dsg_serving=DSGServingConfig(refresh_interval=4)),
    "chunk4": dict(PAGED, decode_chunk=4),
}


@pytest.fixture(scope="module")
def engine_parts():
    return make_engine_parts()


def run_engine(parts, kind, n=6):
    reqs = mixed_traffic(parts[0], n=n)
    _, eng = run_and_collect(engine_spec(*parts, **ENGINES[kind]), reqs,
                             return_engine=True)
    return eng, reqs


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_span_tree(engine_parts, kind):
    eng, reqs = run_engine(engine_parts, kind)
    spans = eng.telemetry.spans()
    sid = {s.sid: s for s in spans}
    named = by_name(spans)

    def parent(s):
        return sid[s.parent].name if s.parent >= 0 else None

    steps = named["repro.engine.step"]
    decode = [s for s in steps if s.attrs["lanes"]]
    assert all(set(s.attrs) == {"step", "lanes", "admits"} for s in steps)
    assert [s.attrs["step"] for s in steps] == sorted(
        s.attrs["step"] for s in steps)
    assert all(parent(s) is None for s in steps)
    for name in ("repro.engine.begin",):
        assert len(named[name]) == len(steps)
        assert all(parent(s) == "repro.engine.step" for s in named[name])
    for name in ("repro.engine.dispatch", "repro.engine.sync",
                 "repro.engine.commit"):
        assert len(named[name]) == len(decode)
        assert {s.parent for s in named[name]} == {s.sid for s in decode}
    assert all({"live_pages", "kv_blocks"} <= set(s.attrs)
               for s in named["repro.engine.dispatch"])
    # one growth pass per step that has active lanes, inside begin
    assert len(named["repro.kv.grow"]) == len(decode)
    assert all(parent(s) == "repro.engine.begin"
               for s in named["repro.kv.grow"])
    # admissions: one each, inside begin, with their write and device wait
    admits = named["repro.engine.admit"]
    assert sorted(s.attrs["uid"] for s in admits) == [r.uid for r in reqs]
    assert sum(s.attrs["admits"] for s in steps) == len(reqs)
    assert all(parent(s) == "repro.engine.begin" for s in admits)
    assert all(s.attrs["bucket"] in eng.buckets for s in admits)
    for name in ("repro.kv.write", "repro.engine.first_token"):
        assert len(named[name]) == len(reqs)
        assert all(parent(s) == "repro.engine.admit" for s in named[name])
    assert all(s.attrs["pages"] > 0 for s in named["repro.kv.write"])
    # the queue wait: in memory only, at the top, ending where admission
    # starts
    queued = {s.attrs["uid"]: s for s in named["repro.request.queued"]}
    assert sorted(queued) == [r.uid for r in reqs]
    for s in admits:
        q = queued[s.attrs["uid"]]
        assert q.parent == -1 and q.t1 == s.t0 and q.t0 <= q.t1
    # retirements: one kv.free each, inside the commit that retired it
    frees = named["repro.kv.free"]
    assert len(frees) == len(reqs)
    assert all(parent(s) == "repro.engine.commit" for s in frees)
    commits = named["repro.engine.commit"]
    assert sum(s.attrs["retired"] for s in commits) == len(reqs)
    if kind == "dsg":
        refresh = named["repro.dsg.refresh"]
        assert refresh and all(parent(s) == "repro.engine.step"
                               for s in refresh)
        # a due lane that retired in the same step is not rewritten
        assert sum(s.attrs["lanes"] for s in refresh) >= 1
    else:
        assert "repro.dsg.refresh" not in named
    # children lie inside their parents, and self times are not negative
    for s in spans:
        if s.parent >= 0:
            p = sid[s.parent]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1
    own = telemetry.self_seconds(spans)
    assert min(own.values()) >= 0
    step_own = sum(own[s.sid] for s in steps)
    assert step_own < sum(s.seconds for s in steps)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_decode_seconds_is_dispatch_plus_sync(engine_parts, kind):
    eng, _ = run_engine(engine_parts, kind)
    named = by_name(eng.telemetry.spans())
    spans = named["repro.engine.dispatch"] + named["repro.engine.sync"]
    assert eng.decode_seconds == pytest.approx(
        sum(s.seconds for s in spans), rel=1e-9)
    assert eng.decode_tok_per_s() > 0


def test_steady_step_records_six_spans(engine_parts):
    """A decode step with no admission and no retirement records the step,
    begin, growth, dispatch, sync and commit: per phase, never per lane."""
    eng, _ = run_engine(engine_parts, "dense", n=2)
    spans = eng.telemetry.spans()
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)

    def size(s):
        return 1 + sum(size(c) for c in kids.get(s.sid, []))

    steady = [s for s in spans if s.name == "repro.engine.step"
              and s.attrs["lanes"] == eng.n_slots and not s.attrs["admits"]
              and not any(c.attrs.get("retired")
                          for c in kids.get(s.sid, []))]
    assert steady
    assert {size(s) for s in steady} == {6}


def test_dsg_flop_model_lives_in_counters(engine_parts):
    eng, _ = run_engine(engine_parts, "dsg")
    rt = eng.dsg_rt
    assert not hasattr(rt, "step_log")
    assert rt.telemetry is eng.telemetry
    c = eng.telemetry.counters
    assert c["dsg.steps"] == eng.steps
    st = rt.flop_stats()
    assert (st["steps"], st["dense_units"], st["csr_units"],
            st["bound_units"]) == (c["dsg.steps"], c["dsg.dense_units"],
                                   c["dsg.csr_units"], c["dsg.bound_units"])
    eng.telemetry.count("other")
    eng.telemetry.reset_counters("dsg.")
    assert dict(c) == {"other": 1}
    with pytest.raises(ValueError, match="no decode steps"):
        rt.flop_stats()


def test_ring_stays_at_capacity():
    tel = telemetry.Telemetry()
    n = telemetry.CAPACITY + 100
    for i in range(n):
        with tel.span("s", i=i):
            pass
    spans = tel.spans()
    assert len(spans) == telemetry.CAPACITY
    assert spans[0].attrs["i"] == 100 and spans[-1].attrs["i"] == n - 1
    assert spans[-1].sid == n - 1


def test_spans_in_an_interval_and_self_time():
    tel = telemetry.Telemetry()
    with tel.span("outer") as outer:
        with tel.span("inner") as inner:
            pass
        inner.attrs["late"] = True
    tel.record("after", outer.t1 + 1.0, outer.t1 + 2.0, uid=7)
    got = tel.spans()
    assert [s.name for s in got] == ["inner", "outer", "after"]
    assert got[0].parent == got[1].sid and got[1].parent == -1
    assert got[2].parent == -1 and got[2].attrs == {"uid": 7}
    assert got[0].attrs == {"late": True}
    assert tel.spans(outer.t0, outer.t1) == got[:2]
    assert tel.spans(name="inner") == got[:1]
    own = telemetry.self_seconds(got)
    assert own[got[1].sid] == pytest.approx(outer.seconds - inner.seconds)
    assert own[got[2].sid] == pytest.approx(1.0)


def test_a_span_records_through_an_exception():
    tel = telemetry.Telemetry()
    with pytest.raises(KeyError):
        with tel.span("outer"):
            with tel.span("inner"):
                raise KeyError
    with tel.span("next"):
        pass
    got = tel.spans()
    assert [s.name for s in got] == ["inner", "outer", "next"]
    assert got[2].parent == -1


def test_registry_holds_live_engines_only(engine_parts):
    eng, _ = run_engine(engine_parts, "dense", n=1)
    tel = eng.telemetry
    assert tel in telemetry.recorders()
    assert eng.backend.telemetry is tel
    del eng
    gc.collect()
    assert tel in telemetry.recorders()      # still referenced here
    tid = id(tel)
    del tel
    gc.collect()
    assert tid not in {id(r) for r in telemetry.recorders()}


def test_threaded_engines_keep_their_own_recorders(engine_parts):
    reqs = mixed_traffic(engine_parts[0], n=8)
    spec = engine_spec(*engine_parts, n_replicas=2, exec_mode="threaded",
                       **PAGED)
    _, router = run_and_collect(spec, reqs, max_steps=100_000,
                                return_engine=True)
    try:
        tels = [e.telemetry for e in router.engines]
        assert tels[0] is not tels[1]
        uids = []
        for eng, tel in zip(router.engines, tels):
            spans = tel.spans()
            sid = {s.sid for s in spans}
            # every parent is a span of the same recorder
            assert all(s.parent == -1 or s.parent in sid for s in spans)
            steps = [s for s in spans if s.name == "repro.engine.step"
                     and s.attrs["lanes"]]
            assert len(steps) == eng.steps
            uids += [s.attrs["uid"]
                     for s in tel.spans(name="repro.engine.admit")]
        assert sorted(uids) == [r.uid for r in reqs]
        assert {u for u, _ in router.dispatch_log} == set(uids)
    finally:
        router.close()
