"""Deep coverage for the fault-tolerance substrate (ISSUE 8 satellite):
straggler detection on injected delays, exactly-once fault injection,
bit-exact restart-from-checkpoint, the serving chaos injector's unit
behavior, and elastic re-planning invariants.

test_substrate.py holds the original smoke coverage; this file pins the
contracts the serving failover path (tests/test_router_faults.py) and
bench_router_faults.py lean on.
"""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.runtime.elastic import ElasticPlan, plan_after_loss
from repro.runtime.fault_tolerance import (FAULT_KINDS, POISON_TOKEN,
                                           FaultInjector, InjectedFault,
                                           ReplicaFault,
                                           ServingFaultInjector,
                                           StragglerMonitor,
                                           run_with_restarts)


# -- straggler monitor -------------------------------------------------------

def test_straggler_monitor_flags_injected_delays():
    mon = StragglerMonitor(window=16, factor=1.5)
    delayed = {12, 17}
    for step in range(20):
        seconds = 0.10 if step not in delayed else 0.35
        flagged = mon.record(step, seconds)
        assert flagged == (step in delayed)
    assert [f[0] for f in mon.flagged] == sorted(delayed)
    for step, seconds, median in mon.flagged:
        assert seconds > 1.5 * median


def test_straggler_monitor_needs_history():
    """No flags until the rolling median has >= 8 samples — a cold
    monitor must not flag the first jit-compile step."""
    mon = StragglerMonitor()
    assert not mon.record(0, 100.0)
    for step in range(1, 8):
        mon.record(step, 0.1)
    assert mon.record(8, 100.0)        # 9th sample: median established


def test_straggler_monitor_rolling_window():
    """The median tracks the WINDOW, not all history: after a regime
    change to uniformly slower steps, the old fast median ages out and
    the slower steps stop being flagged."""
    mon = StragglerMonitor(window=8, factor=1.5)
    for step in range(8):
        mon.record(step, 0.1)
    assert mon.record(8, 0.3)          # slow vs the fast window
    for step in range(9, 17):
        mon.record(step, 0.3)          # new normal fills the window
    assert not mon.record(17, 0.3)


# -- training-side fault injection -------------------------------------------

def test_fault_injector_fires_exactly_once_per_step():
    inj = FaultInjector(fail_at=(3,))
    with pytest.raises(RuntimeError, match="injected failure at step 3"):
        inj.maybe_fail(3)
    inj.maybe_fail(3)                  # replay after restore: no re-fire
    inj.maybe_fail(4)


def test_run_with_restarts_restore_is_bit_exact(tmp_path):
    """A run that crashes at step 30 and restores from the step-20
    checkpoint replays 20..29 and lands bit-identical to a fault-free
    run — the make_batch(step) purity contract."""
    def step_fn(state, batch):
        s = state["x"] * 1.000001 + batch
        return {"x": s}, {"loss": float(np.sum(s))}

    def make_batch(step):
        return np.full(4, step, dtype=np.float64)

    clean, _ = run_with_restarts(
        step_fn=step_fn, state={"x": np.zeros(4)}, make_batch=make_batch,
        ckpt=None, total_steps=40)

    mgr = CheckpointManager(str(tmp_path), keep=3)
    # leg 1 writes the step-20 checkpoint and waits for the async flush
    mid, _ = run_with_restarts(
        step_fn=step_fn, state={"x": np.zeros(4)}, make_batch=make_batch,
        ckpt=mgr, total_steps=20, ckpt_every=20)
    # leg 2 crashes at step 30, restores step 20, replays 20..29
    faulty, hist = run_with_restarts(
        step_fn=step_fn, state=mid, make_batch=make_batch,
        ckpt=mgr, total_steps=40, start_step=20, ckpt_every=1000,
        injector=FaultInjector(fail_at=(30,)))
    np.testing.assert_array_equal(clean["x"], faulty["x"])
    # steps 20..29 ran twice (before the crash, then replayed)
    assert [h["step"] for h in hist].count(25) == 2


def test_run_with_restarts_exhausts_retries():
    inj = FaultInjector(fail_at=(2,), exc=OSError)
    calls = []

    def bad(state, batch):
        calls.append(batch)
        raise ValueError("persistent")

    with pytest.raises(ValueError, match="persistent"):
        run_with_restarts(step_fn=bad, state={}, make_batch=lambda s: s,
                          ckpt=None, total_steps=4, max_retries=2)
    assert len(calls) == 3             # initial + 2 retries, then raise
    with pytest.raises(OSError):       # injector exc type respected
        run_with_restarts(step_fn=lambda s, b: (s, {}), state={},
                          make_batch=lambda s: s, ckpt=None,
                          total_steps=4, max_retries=0, injector=inj)


# -- serving chaos injector ---------------------------------------------------

def _fake_engine(replica=0, steps=0, outputs=(), statuses=None):
    statuses = statuses or ["pending"] * len(outputs)
    slots = [SimpleNamespace(req=SimpleNamespace(output=list(o), status=st)
                             if o is not None else None)
             for o, st in zip(outputs, statuses)]
    # mirror the ServingEngine fields on_step()/attach() touch
    return SimpleNamespace(replica_index=replica, steps=steps,
                           slots=slots, fault_injector=None)


def test_replica_fault_validation():
    assert FAULT_KINDS == ("kill", "delay", "poison")
    with pytest.raises(ValueError, match="unknown fault kind"):
        ReplicaFault(replica=0, step=0, kind="explode")
    with pytest.raises(ValueError, match=">= 0"):
        ReplicaFault(replica=-1, step=0)
    with pytest.raises(ValueError, match=">= 0"):
        ReplicaFault(replica=0, step=-2)
    # tuple coercion in the injector ctor
    inj = ServingFaultInjector([(1, 4), (0, 2, "delay", 0.01)])
    assert inj.faults[0] == ReplicaFault(replica=1, step=4)
    assert inj.faults[1].kind == "delay"


def test_serving_injector_keys_on_replica_and_step():
    inj = ServingFaultInjector([ReplicaFault(replica=1, step=3)])
    inj.on_step(_fake_engine(replica=0, steps=3))   # wrong replica
    inj.on_step(_fake_engine(replica=1, steps=2))   # wrong step
    assert inj.log == []
    with pytest.raises(InjectedFault, match="replica 1 step 3"):
        inj.on_step(_fake_engine(replica=1, steps=3))
    assert inj.log == [{"replica": 1, "step": 3, "kind": "kill"}]
    # exactly once: the restarted replica passes step 3 again unharmed
    inj.on_step(_fake_engine(replica=1, steps=3))
    assert len(inj.log) == 1
    inj.reset()                        # re-armed for a benchmark repeat
    with pytest.raises(InjectedFault):
        inj.on_step(_fake_engine(replica=1, steps=3))


def test_serving_injector_delay_sleeps_without_raising():
    inj = ServingFaultInjector(
        [ReplicaFault(replica=0, step=1, kind="delay", delay_s=0.05)])
    eng = _fake_engine(steps=1)
    t0 = time.perf_counter()
    inj.on_step(eng)                   # no raise
    assert time.perf_counter() - t0 >= 0.05
    assert inj.log[0]["kind"] == "delay"


def test_serving_injector_poison_corrupts_resident_lanes():
    inj = ServingFaultInjector(
        [ReplicaFault(replica=0, step=2, kind="poison")])
    eng = _fake_engine(steps=2, outputs=([5, 6], None, [], [7, 8]),
                       statuses=["pending", None, "pending", "ok"])
    with pytest.raises(InjectedFault, match="poison"):
        inj.on_step(eng)
    assert eng.slots[0].req.output == [5, POISON_TOKEN]
    assert eng.slots[2].req.output == []       # nothing emitted yet
    # a complete stream whose lane awaits its last decode's commit
    assert eng.slots[3].req.output == [7, 8]


def test_serving_injector_attach_detach():
    inj = ServingFaultInjector([])
    engines = [_fake_engine(), _fake_engine()]
    inj.attach(engines)
    assert [e.replica_index for e in engines] == [0, 1]
    assert all(e.fault_injector is inj for e in engines)
    other = ServingFaultInjector([])
    other.attach([engines[1]])
    inj.detach(engines)                # only detaches its own hookups
    assert engines[0].fault_injector is None
    assert engines[1].fault_injector is other


# -- elastic re-planning ------------------------------------------------------

@pytest.mark.parametrize("available,model", [
    (496, 16), (300, 16), (17, 16), (64, 8), (1, 1), (1023, 4)])
def test_plan_after_loss_invariants(available, model):
    p = plan_after_loss(available, model=model)
    assert p.model == model                      # model axis intact
    assert p.data & (p.data - 1) == 0            # power-of-two data axis
    assert p.n_devices == p.data * model
    assert p.n_devices + p.dropped == available  # device accounting
    assert p.data * 2 * model > available        # largest such pow2
    assert 0.0 < p.scale <= 1.0


def test_plan_after_loss_raises_below_model_axis():
    with pytest.raises(RuntimeError, match="cannot keep model=16"):
        plan_after_loss(15, model=16)


def test_plan_scale_reflects_dropped_fraction():
    p = ElasticPlan(n_devices=256, data=16, model=16, dropped=256)
    assert p.scale == pytest.approx(0.5)
