"""One decode in flight (ServingEngine.step): the engine dispatches decode
n+1 on decode n's tokens while they are still on the device, then reads and
commits decode n.  These tests hold it to the synchronous composition
(begin_step -> jitted decode -> commit, each step read before the next is
built, as the sharded executor drives it): the same streams, statuses and
retirement order, the same page pool afterwards, nothing left behind by
eviction, reset or an abort with a decode in flight, and the counters that
say how often it engages.  All on the CPU at smoke size.
"""
import jax
import numpy as np
import pytest

from harness import make_engine_parts, smoke_cfg
from repro.models import api
from repro.serving.dsg_runtime import DSGServingConfig
from repro.serving.scheduler import EngineAborted, Request, ServingEngine

PAGED = dict(n_slots=2, max_seq=64, prompt_bucket=32, cache_backend="paged",
             page_size=8, cache_tokens=160)


@pytest.fixture(scope="module")
def models():
    dense = make_engine_parts()
    cfg = smoke_cfg("deepseek-moe-16b")
    cfg = cfg.replace(dsg=cfg.dsg._replace(enabled=False))
    return {"dense": dense,
            "moe": (cfg, api.init_model(jax.random.PRNGKey(0), cfg), None)}


def traffic(vocab, *, n=5, temperature=0.0, seed=3, eos=None):
    rng = np.random.default_rng(seed)
    return [Request(uid=u, max_new=int(rng.integers(3, 12)),
                    prompt=rng.integers(0, vocab, int(rng.integers(4, 30)),
                                        dtype=np.int32),
                    temperature=temperature, top_p=0.9,
                    eos_id=None if eos is None else eos.get(u))
            for u in range(n)]


def engine(parts, **kw):
    return ServingEngine(*parts, **{**PAGED, **kw})


def run_sync(eng, reqs):
    """The synchronous composition: every decode is read and committed
    before the next plan is built."""
    for r in reqs:
        eng.submit(r)
    while eng.queue_depth() or eng.busy_slots():
        plan = eng.begin_step()
        *outs, eng.cache, _ = eng._jit_decode[plan.sample](
            eng.params, eng.dsg, *eng.decode_operands(plan, plan.sample),
            refresh=plan.refresh)
        eng.commit(plan, [np.asarray(o) for o in outs], 0.0)
    return eng.done


def run_ahead(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return eng.run()


def outcome(done):
    """Streams, statuses and the order requests retired in."""
    return [(u, r.status, list(r.output)) for u, r in done.items()]


def full_pool(eng):
    alloc = eng.backend.allocator
    return alloc.n_pages - alloc.reserved


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("model", ["dense", "moe"])
def test_streams_match_the_synchronous_order(models, model, temperature):
    """Greedy and sampled streams, statuses and retirement order of run()
    equal the synchronous composition's, token for token, over traffic
    that retires and readmits lanes; most decodes were dispatched
    ahead."""
    parts = models[model]
    vocab = parts[0].vocab
    want = outcome(run_sync(engine(parts),
                            traffic(vocab, temperature=temperature)))
    eng = engine(parts)
    got = outcome(run_ahead(eng, traffic(vocab, temperature=temperature)))
    assert got == want
    assert len(got) == 5 and all(s == "ok" for _, s, _ in got)
    c = eng.telemetry.counters
    assert c["engine.ahead_steps"] > eng.steps / 2
    assert c["engine.ahead_steps"] + c["engine.drains"] == eng.steps
    assert c["engine.lanes_past_eos"] == 0
    assert eng.backend.allocator.free_pages == full_pool(eng)


def guarded_free(eng):
    """Fail if a lane's pages are freed while a dispatched, unread decode
    still writes them."""
    free = eng.backend.free

    def checked(handle, slot):
        if eng._inflight is not None:
            assert slot not in eng._inflight.plan.active, slot
        return free(handle, slot)

    eng.backend.free = checked


@pytest.mark.parametrize("model", ["dense", "moe"])
def test_eos_under_a_dispatched_step(models, model):
    """A stop token that the host reads only after the next decode was
    dispatched on it ends its stream with the token and nothing after
    it; that lane-step is counted and discarded, no page is freed under
    it, and the pool after drain() equals the synchronous order's.  Two
    requests on two lanes: no admission drains the pipeline mid-run, so
    every stop token arrives under a dispatched step."""
    parts = models[model]
    vocab = parts[0].vocab
    plain = run_ahead(engine(parts), traffic(vocab, n=2))
    # each request stops at its first new token after the admission's,
    # short of its budget
    eos, cut = {}, {}
    for u, r in plain.items():
        for k in range(1, len(r.output) - 1):
            if r.output[k] not in r.output[:k]:
                eos[u], cut[u] = r.output[k], k + 1
                break
    assert eos
    want_eng = engine(parts)
    want = outcome(run_sync(want_eng, traffic(vocab, n=2, eos=eos)))
    eng = engine(parts)
    guarded_free(eng)
    got = outcome(run_ahead(eng, traffic(vocab, n=2, eos=eos)))
    assert got == want
    for u, _, out in got:
        assert out == plain[u].output[:cut.get(u, len(plain[u].output))]
    c = eng.telemetry.counters
    assert c["engine.lanes_past_eos"] == len(eos)
    assert want_eng.telemetry.counters["engine.lanes_past_eos"] == 0
    assert eng.backend.allocator.free_pages == \
        want_eng.backend.allocator.free_pages == full_pool(eng)
    assert eng.free_slots() == eng.n_slots and eng._inflight is None


def _in_flight(models):
    parts = models["dense"]
    eng = engine(parts, n_slots=3)
    reqs = traffic(parts[0].vocab, n=5)
    for r in reqs:
        r.max_new = 20
        eng.submit(r)
    for _ in range(4):
        eng.step()
    assert eng._inflight is not None and eng.busy_slots() == 3
    return eng, reqs


@pytest.mark.parametrize("how", ["evict_slot", "reset", "abort"])
def test_nothing_left_behind_with_a_step_in_flight(models, how):
    """Evicting every lane, a reset, or an abort followed by the router's
    reset, each with a decode in flight, leaves no lane, page, pending
    output or decode behind: the requests it returns keep their outputs
    through later steps, and the engine serves new traffic as a fresh
    one does."""
    eng, reqs = _in_flight(models)
    if how == "evict_slot":
        out = [eng.evict_slot(i) for i in range(eng.n_slots)]
        assert [r.uid for r in out] == [0, 1, 2]
        eng.queue.clear()
    elif how == "reset":
        out = eng.reset()
        assert [r.uid for r in out] == [0, 1, 2, 3, 4]
    else:
        eng.abort = True
        with pytest.raises(EngineAborted):
            eng.step()
        assert eng._inflight is not None    # the router's reset follows
        out = eng.reset()
    kept = {r.uid: list(r.output) for r in out}
    assert all(r.status == "pending" for r in out)
    assert eng._inflight is None and eng.free_slots() == eng.n_slots
    assert eng.queue_depth() == 0
    assert eng.free_pages() == full_pool(eng)
    eng.step()
    assert {r.uid: list(r.output) for r in out} == kept
    # replayed from their prompts, the requests decode as on a fresh engine
    fresh = engine(models["dense"], n_slots=3)
    want = {}
    for r in out:
        want[r.uid] = Request(uid=r.uid, prompt=r.prompt, max_new=20)
        r.output.clear()
    assert {u: r.output for u, r in run_ahead(eng, out).items()
            if u in want} == {u: r.output for u, r in
                              run_ahead(fresh, want.values()).items()}


@pytest.mark.parametrize("kind", ["dense", "dsg"])
def test_steady_batch_steps_ahead(models, kind):
    """On a steady batch at least 85% of a plain engine's steps dispatch
    ahead of the previous step's read, and each such step says so; a DSG
    engine, whose next CSR operand needs the host's read of the refresh
    scores, never does."""
    parts = models["dense"]
    kw = dict(n_slots=4, cache_tokens=400)
    if kind == "dsg":
        kw["dsg_serving"] = DSGServingConfig(refresh_interval=4)
    eng = engine(parts, **kw)
    reqs = traffic(parts[0].vocab, n=8)
    for r in reqs:
        r.max_new = 40
    run_ahead(eng, reqs)
    steps = [s for s in eng.telemetry.spans(name="repro.engine.step")
             if s.attrs["lanes"]]
    ahead = [s.attrs["ahead"] for s in steps]
    c = eng.telemetry.counters
    assert len(steps) == eng.steps
    assert sum(ahead) == c["engine.ahead_steps"]
    if kind == "dsg":
        assert not any(ahead) and c["engine.drains"] == eng.steps
    else:
        assert sum(ahead) / len(steps) >= 0.85
