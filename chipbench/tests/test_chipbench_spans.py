"""The engine's spans as the benchmark reads them, at test size on the CPU.

Per step, what the spans say (active lanes, admissions) matches the
`StepRecord` that `chipbench.drivers` wrote after the step returned; a
profiler trace holds the `repro.*` spans inside `chipbench.step` on one
timeline; and the three readers of the spans return numbers on a run, and
nothing where the window holds no step or the program records no spans.
"""
import glob
import os
import sys

import jax
import pytest

from chipbench import bench, drivers, report, run, traffic
from chipbench.tests import tiny
from chipbench.weights import make_weights
from repro.serving.scheduler import Request

READERS = ("host_ms.batch", "host_ms.chat", "admit_ms.chat")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def make_request(spec):
    return Request(uid=spec.uid, prompt=spec.prompt, max_new=spec.max_new)


def driven(root, cell, n=6, seed=2**31 + 11):
    """A `drivers.Driver` that has run `n` requests of the cell's mix to
    the end."""
    c = bench.resolve(cell, root)
    w = make_weights(c.config, seed)
    eng = run.build_engine(c.config, w, 3)
    gen = traffic.Traffic(c.traffic, seed, c.config["vocab_size"])
    drv = drivers.Driver(eng, gen, make_request)
    for spec in gen.take(n):
        drv.submit(spec, drivers.CLOCK())
    while drv.busy():
        drv.step()
    return drv


def reader(root, name):
    cell = "tiny.tiny_chat" if name.endswith("chat") else "tiny.tiny_batch"
    return bench.resolve(cell, root).reader({"name": name})


def ctx_for(drv, steps):
    return report.Ctx(cfg={}, mix={}, peaks={}, setup_s=0.0,
                      window=(steps[0].start, steps[-1].end) if steps
                      else (0.0, 0.0),
                      seen=drv.everyone(), steps=drv.steps,
                      traced_steps=steps)


@pytest.mark.parametrize("cell", ["tiny.tiny_batch", "tiny-dsg.tiny_batch"])
def test_spans_agree_with_the_step_records(root, cell):
    drv = driven(root, cell)
    spans = drv.engine.telemetry.spans(name="repro.engine.step")
    assert len(spans) == len(drv.steps)
    for rec, s in zip(drv.steps, spans):
        assert rec.start <= s.t0 <= s.t1 <= rec.end
        assert s.attrs["lanes"] == rec.lanes
        assert s.attrs["admits"] == rec.admits
    assert sum(r.admits for r in drv.steps) == 6


def test_profiler_trace_nests_program_spans_in_chipbench_step(root, tmp_path):
    from jax.profiler import ProfileData
    drv = driven(root, "tiny.tiny_batch", n=2)
    for spec in drv.traffic.take(2):
        drv.submit(spec, drivers.CLOCK())
    drv.step()                      # admissions compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        drv.step()
        drv.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("chipbench.", "repro.")):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns,
                         ev.start_ns + ev.duration_ns))
    outer = events["chipbench.step"]
    assert len(outer) == 2
    for name in ("repro.engine.step", "repro.engine.begin",
                 "repro.engine.dispatch", "repro.engine.sync",
                 "repro.engine.commit", "repro.kv.grow"):
        assert len(events[name]) == 2, name
        for line, s, e in events[name]:
            assert any(ol == line and os_ <= s and e <= oe
                       for ol, os_, oe in outer), name


@pytest.mark.parametrize("name", READERS)
def test_readers_read_a_run(root, name):
    cell = "tiny.tiny_chat" if name.endswith("chat") else "tiny.tiny_batch"
    drv = driven(root, cell)
    value = reader(root, name)(ctx_for(drv, drv.steps))
    assert value is not None and value > 0
    if name.startswith("host_ms"):
        steps = drv.engine.telemetry.spans(name="repro.engine.step")
        longest = max(s.seconds for s in steps)
        assert value < 1e3 * longest


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_step(root, name):
    drv = driven(root, "tiny.tiny_batch", n=1)
    read = reader(root, name)
    assert read(ctx_for(drv, [])) is None
    before = drivers.StepRecord(start=0.0, end=1e-9)   # before any span
    assert read(ctx_for(drv, [before])) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_spans(root, name,
                                                           monkeypatch):
    import repro.serving
    drv = driven(root, "tiny.tiny_chat", n=2)
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    monkeypatch.delattr(repro.serving, "telemetry")
    assert reader(root, name)(ctx_for(drv, drv.steps)) is None
