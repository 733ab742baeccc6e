"""repro-lint: checker behavior on a fixture corpus + the live tree.

Each known-bad snippet is written into a throwaway package and run
through `run_lint`; the checker must produce EXACTLY the expected
finding (no more — false positives on the paired known-good snippet are
failures too).  The live-tree test pins src/ clean against the
checked-in baseline, so a genuine new violation fails the suite the
same way it fails CI's lint job.

The REPRO_TSAN tests exercise the dynamic half of the lock-discipline
contract (analysis/contracts.py): the guarded containers raise
TsanViolation on undisciplined mutations and stay silent under the
documented protocol.
"""
import collections
import json
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from repro.analysis.callgraph import Index
from repro.analysis.contracts import (CheckedCondition, GuardedDeque,
                                      GuardedDict, GuardedList,
                                      TsanViolation)
from repro.analysis.findings import Finding, load_baseline
from repro.analysis.runner import run_lint

REPO = Path(__file__).resolve().parent.parent


def lint_snippet(tmp_path, source, filename="mod.py"):
    root = tmp_path / "src"
    root.mkdir(exist_ok=True)
    (root / filename).write_text(source)
    return run_lint(root)


def codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# jit hygiene
# ---------------------------------------------------------------------------

BAD_HOST_SYNC = """\
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    y = jnp.sum(x)
    return y.item()
"""

BAD_COERCION = """\
import jax

@jax.jit
def f(x):
    return float(x * 2)
"""

BAD_BRANCH = """\
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    if jnp.sum(x) > 0:
        return x
    return -x
"""

BAD_CLOSURE = """\
import jax

class Engine:
    def __init__(self):
        self.steps = 0
        def _step(tok):
            return tok + self.steps
        self._jit_step = jax.jit(_step)
"""

GOOD_JIT = """\
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, static_argnames=("block",))
def f(x, block):
    if block > 128:          # static arg: host branch is fine
        x = x * 2
    n = x.shape[0]           # attribute access kills taint
    return jnp.sum(x) / n
"""


def test_host_sync_in_jit(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_HOST_SYNC)) == ["JIT101"]


def test_coercion_of_traced_value(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_COERCION)) == ["JIT102"]


def test_branch_on_tracer(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_BRANCH)) == ["JIT104"]


def test_jitted_closure_captures_self(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_CLOSURE)) == ["JIT105"]


def test_static_branching_is_clean(tmp_path):
    assert lint_snippet(tmp_path, GOOD_JIT) == []


def test_non_hashable_static_default(tmp_path):
    src = (
        "import jax\n"
        "from functools import partial\n\n"
        "@partial(jax.jit, static_argnames=('shape',))\n"
        "def f(x, shape=[1, 2]):\n"
        "    return x\n")
    assert codes(lint_snippet(tmp_path, src)) == ["JIT106"]


# ---------------------------------------------------------------------------
# lock discipline
# ---------------------------------------------------------------------------

BAD_UNLOCKED = """\
import threading
from repro.analysis.contracts import locked_by

@locked_by("_cond", "_idle")
class Executor:
    def __init__(self):
        self._cond = threading.Condition()
        self._idle = [True]

    def park(self, i):
        self._idle[i] = True            # no lock: LCK201
"""

GOOD_LOCKED = """\
import threading
from repro.analysis.contracts import locked_by, owned_by, runs_on, exempt

@locked_by("_cond", "_idle")
@owned_by("worker", "queue")
class Executor:
    def __init__(self):
        self._cond = threading.Condition()
        self._idle = [True]
        self.queue = []

    def park(self, i):
        with self._cond:
            self._idle[i] = True        # locked: fine

    @runs_on("worker")
    def admit(self):
        self.queue.pop()                # owner role: fine

    @exempt("queue", reason="external entry; serialized upstream")
    def submit(self, r):
        self.queue.append(r)            # waived with a reason: fine
"""


def test_unlocked_mutation_flagged(tmp_path):
    found = lint_snippet(tmp_path, BAD_UNLOCKED)
    assert codes(found) == ["LCK201"]
    assert "_idle" in found[0].message


def test_lock_discipline_clean(tmp_path):
    assert lint_snippet(tmp_path, GOOD_LOCKED) == []


def test_owned_field_outside_owner(tmp_path):
    src = (
        "from repro.analysis.contracts import owned_by\n\n"
        "@owned_by('worker', 'done')\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.done = {}\n"
        "    def merge(self, k, v):\n"
        "        self.done[k] = v\n")
    assert codes(lint_snippet(tmp_path, src)) == ["LCK202"]


# ---------------------------------------------------------------------------
# pallas contracts
# ---------------------------------------------------------------------------

BAD_ENV_READ = """\
import os

def use_interpret():
    return os.environ.get("REPRO_INTERPRET", "") == "1"
"""


def test_raw_interpret_read_flagged(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_ENV_READ)) == ["PAL301"]


def test_interpret_read_allowed_in_ops(tmp_path):
    root = tmp_path / "src" / "repro" / "kernels"
    root.mkdir(parents=True)
    (root / "ops.py").write_text(BAD_ENV_READ)
    assert run_lint(tmp_path / "src") == []


def test_traced_grid_flagged(tmp_path):
    src = (
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n\n"
        "def run(x, kernel):\n"
        "    return pl.pallas_call(\n"
        "        kernel, out_shape=x,\n"
        "        grid=(jnp.ceil(x.shape[0] / 8),))(x)\n")
    assert codes(lint_snippet(tmp_path, src)) == ["PAL302"]


def test_host_numpy_index_map_flagged(tmp_path):
    src = (
        "import numpy as np\n"
        "from jax.experimental import pallas as pl\n\n"
        "spec = pl.BlockSpec((8, 8), lambda i: (np.int32(i), 0))\n")
    assert codes(lint_snippet(tmp_path, src)) == ["PAL303"]


BAD_INTERPRET_LITERAL = """\
from jax.experimental import pallas as pl

def run(x, kernel):
    return pl.pallas_call(kernel, out_shape=x, interpret=True)(x)
"""

GOOD_INTERPRET_THREADED = """\
from jax.experimental import pallas as pl

def run(x, kernel, interpret=False):
    return pl.pallas_call(kernel, out_shape=x, interpret=interpret)(x)
"""


def test_interpret_literal_outside_kernels_flagged(tmp_path):
    assert codes(lint_snippet(tmp_path, BAD_INTERPRET_LITERAL)) \
        == ["PAL304"]


def test_interpret_literal_allowed_in_kernels(tmp_path):
    # kernel modules DEFAULT the kwarg (interpret: bool = False) and the
    # ops.py wrappers thread the policy — a literal there is the
    # documented layering, not a fork
    root = tmp_path / "src" / "repro" / "kernels"
    root.mkdir(parents=True)
    (root / "mod.py").write_text(BAD_INTERPRET_LITERAL)
    assert run_lint(tmp_path / "src") == []


def test_interpret_threaded_variable_is_clean(tmp_path):
    assert lint_snippet(tmp_path, GOOD_INTERPRET_THREADED) == []


def test_clamped_index_map_is_clean(tmp_path):
    # jnp clamps inside index maps are the paged-attention idiom: index
    # maps are traced, so jnp is legal there (and np is legal in grids)
    src = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n\n"
        "def run(x, kernel, n):\n"
        "    spec = pl.BlockSpec((8, 8), lambda i, r: (jnp.minimum(i, r), 0))\n"
        "    return pl.pallas_call(kernel, out_shape=x,\n"
        "                          grid=(int(np.ceil(n / 8)),))(x)\n")
    assert lint_snippet(tmp_path, src) == []


# ---------------------------------------------------------------------------
# pytree registration
# ---------------------------------------------------------------------------

BAD_PYTREE = """\
import jax
from dataclasses import dataclass

@dataclass
class Carry:
    total: object

@jax.jit
def f(x):
    return Carry(total=x.sum())
"""

GOOD_PYTREE = """\
import jax
from dataclasses import dataclass

@jax.tree_util.register_pytree_node_class
@dataclass
class Carry:
    total: object

    def tree_flatten(self):
        return (self.total,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

@jax.jit
def f(x):
    return Carry(total=x.sum())
"""


def test_unregistered_dataclass_flagged(tmp_path):
    found = lint_snippet(tmp_path, BAD_PYTREE)
    assert codes(found) == ["PYT401"]
    assert "Carry" in found[0].message


def test_registered_dataclass_clean(tmp_path):
    assert lint_snippet(tmp_path, GOOD_PYTREE) == []


# ---------------------------------------------------------------------------
# live tree + CLI
# ---------------------------------------------------------------------------

def test_src_tree_clean_against_baseline():
    findings = run_lint(REPO / "src")
    baseline = load_baseline(REPO / "scripts" / "lint_baseline.json")
    new, _ = baseline.split(findings)
    assert new == [], "\n".join(f.render() for f in new)


@pytest.fixture(scope="module")
def src_index():
    return Index.build(REPO / "src")


@pytest.mark.parametrize("mod", ["scheduler", "parallel_exec", "kv_cache"])
def test_serving_jit_sites_resolve(src_index, mod):
    """Every jax.jit call in the serving engine and its executors names a
    function the index resolves (a lambda or a comprehension variable
    does not), so jit_hygiene walks what each program traces."""
    mi = src_index.modules[f"repro.serving.{mod}"]
    found = collections.defaultdict(set)
    for scope, call, target in src_index._jit_sites(mi):
        fn_expr, _ = src_index._unwrap_jit(call)
        fi = target or src_index._resolve_fn_expr(mi, scope, fn_expr)
        found[call.lineno].add(fi and fi.qualname)
    unresolved = sorted(ln for ln, q in found.items() if q == {None})
    assert found and not unresolved, f"{mod}.py lines {unresolved}"


@pytest.mark.parametrize("qualname", [
    "scheduler._micro_step", "scheduler._one_step", "scheduler._chunk_steps",
    "scheduler.sample_tokens", "kv_cache.decode_view",
    "dsg_runtime.mirror_csr"])
def test_decode_step_reached_from_jit_roots(src_index, qualname):
    traced = src_index.traced_functions(src_index.jit_roots())
    assert f"repro.serving.{qualname}" in traced


@pytest.mark.parametrize("snippet", [BAD_HOST_SYNC, BAD_UNLOCKED,
                                     BAD_ENV_READ, BAD_PYTREE],
                         ids=["host-sync", "unlocked", "env-read",
                              "pytree"])
def test_cli_exits_nonzero_on_bad_snippet(tmp_path, snippet):
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(snippet)
    script = str(REPO / "scripts" / "run_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--root", str(bad), "--fail-on-new",
         "--baseline", str(tmp_path / "empty_baseline.json")],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "new finding" in r.stdout


def test_cli_clean_on_src_with_baseline():
    script = str(REPO / "scripts" / "run_lint.py")
    ok = subprocess.run([sys.executable, script, "--fail-on-new"],
                        capture_output=True, text=True, cwd=REPO)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "clean" in ok.stdout


def test_finding_fingerprint_stable_across_line_drift():
    a = Finding(file="m.py", line=10, col=0, code="JIT101",
                checker="jit_hygiene", message="msg", context="m.f")
    b = Finding(file="m.py", line=99, col=4, code="JIT101",
                checker="jit_hygiene", message="msg", context="m.f")
    assert a.fingerprint == b.fingerprint


def test_baseline_reason_required(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"accepted": [
        {"fingerprint": "m.py::JIT101::m.f::msg", "reason": ""}]}))
    baseline = load_baseline(path)
    assert baseline.unreasoned() == ["m.py::JIT101::m.f::msg"]


# ---------------------------------------------------------------------------
# REPRO_TSAN runtime shim
# ---------------------------------------------------------------------------

class StubEngine:
    """Duck-typed engine: just enough surface for ThreadedExecutor."""

    def __init__(self):
        self.queue = collections.deque()
        self.done = {}
        self.slots = []

    def submit(self, req):
        self.queue.append(req)


@pytest.fixture
def tsan_executor(monkeypatch):
    monkeypatch.setenv("REPRO_TSAN", "1")
    from repro.serving.parallel_exec import ThreadedExecutor
    ex = ThreadedExecutor([StubEngine(), StubEngine()])
    yield ex
    ex.close()


def test_tsan_wraps_state(tsan_executor):
    ex = tsan_executor
    assert isinstance(ex._cond, CheckedCondition)
    assert isinstance(ex._idle, GuardedList)
    assert isinstance(ex.busy_seconds, GuardedList)
    assert isinstance(ex.engines[0].queue, GuardedDeque)
    assert isinstance(ex.engines[0].done, GuardedDict)


def test_tsan_allows_locked_and_quiescent_mutation(tsan_executor):
    ex = tsan_executor
    with ex._cond:
        ex._idle[0] = False              # locked: fine
        ex._idle[0] = True
    ex.engines[0].queue.append("r")      # quiescent (no owner): fine
    ex.dispatch(1, "r2")                 # the documented protocol
    assert list(ex.engines[1].queue) == ["r2"]


def test_tsan_catches_unlocked_mutation(tsan_executor):
    ex = tsan_executor
    t = threading.Thread(target=lambda: None)
    t.start(); t.join()
    ex._idle.set_owner(t)                # another thread owns it
    with pytest.raises(TsanViolation, match="_idle"):
        ex._idle[0] = False
    ex._idle.set_owner(None)


def test_tsan_catches_cross_thread_engine_mutation(tsan_executor):
    ex = tsan_executor
    err = []
    ex.engines[0].queue.set_owner(threading.current_thread())

    def intruder():
        try:
            ex.engines[0].queue.append("stolen")
        except TsanViolation as e:
            err.append(e)

    t = threading.Thread(target=intruder)
    t.start(); t.join()
    assert err, "cross-thread unlocked mutation must raise"
    ex.engines[0].queue.set_owner(None)


def test_tsan_wait_requires_lock(tsan_executor):
    with pytest.raises(TsanViolation):
        tsan_executor._cond.wait(0.01)


def test_tsan_off_uses_plain_state(monkeypatch):
    monkeypatch.delenv("REPRO_TSAN", raising=False)
    from repro.serving.parallel_exec import ThreadedExecutor
    ex = ThreadedExecutor([StubEngine()])
    assert type(ex._idle) is list
    assert type(ex.engines[0].queue) is collections.deque
    ex.close()


def test_tsan_reset_timing_rewraps(tsan_executor):
    ex = tsan_executor
    with ex._cond:
        ex.busy_seconds[0] = 1.5
    ex.reset_timing()
    assert isinstance(ex.busy_seconds, GuardedList)
    assert ex.busy_seconds == [0.0, 0.0]


# ---------------------------------------------------------------------------
# workload latency stats (satellite: no more silent 0.0 percentiles)
# ---------------------------------------------------------------------------

def test_latency_stats_raise_on_empty():
    from repro.serving.workload import latency_stats
    with pytest.raises(ValueError, match="finished request"):
        latency_stats({})


def test_latency_stats_percentiles():
    from repro.serving.scheduler import Request
    from repro.serving.workload import latency_stats
    import numpy as np
    done = {}
    for uid, lat in enumerate([1.0, 2.0, 3.0, 4.0]):
        r = Request(uid=uid, prompt=np.zeros(4, np.int32))
        r.submitted, r.finished = 10.0, 10.0 + lat
        r.status = "ok"
        done[uid] = r
    stats = latency_stats(done)
    assert stats["p50_s"] == pytest.approx(2.5)
    assert stats["p95_s"] == pytest.approx(3.85)
    assert stats["ok_requests"] == 4
    assert stats["failed_requests"] == 0
    assert stats["timed_out_requests"] == 0


def test_latency_stats_excludes_non_ok():
    """A timed-out request's finish stamp is exactly its deadline —
    folding it into p50/p95 reports the SLO ceiling as an observed
    latency.  Percentiles must cover status == 'ok' only, with non-ok
    outcomes surfaced as counts."""
    from repro.serving.scheduler import Request
    from repro.serving.workload import latency_stats
    import numpy as np
    done = {}
    for uid, (lat, status) in enumerate(
            [(1.0, "ok"), (2.0, "ok"), (3.0, "ok"), (4.0, "ok"),
             (60.0, "timed_out"), (45.0, "failed")]):
        r = Request(uid=uid, prompt=np.zeros(4, np.int32))
        r.submitted, r.finished = 10.0, 10.0 + lat
        r.status = status
        done[uid] = r
    stats = latency_stats(done)
    # identical to the all-ok run above: the 60s/45s non-ok latencies
    # must not move the percentiles
    assert stats["p50_s"] == pytest.approx(2.5)
    assert stats["p95_s"] == pytest.approx(3.85)
    assert stats["ok_requests"] == 4
    assert stats["failed_requests"] == 1
    assert stats["timed_out_requests"] == 1
    # all-non-ok: percentiles are undefined, not 0.0
    bad = {u: r for u, r in done.items() if r.status != "ok"}
    with pytest.raises(ValueError, match="status"):
        latency_stats(bad)


def _stamped_request(uid, *, submitted=10.0, first=None, finished=None,
                     n_out=0, status="ok"):
    from repro.serving.scheduler import Request
    import numpy as np
    r = Request(uid=uid, prompt=np.zeros(4, np.int32))
    r.submitted = submitted
    r.first_token = 0.0 if first is None else first
    r.finished = finished if finished is not None else submitted + 1.0
    r.output = list(range(n_out))
    r.status = status
    return r


def test_latency_stats_ttft_tpot_split():
    """TTFT = submit -> first token; TPOT = (finish - first token) /
    (output tokens - 1).  Four ok requests with hand-picked stamps pin
    both percentile pairs."""
    from repro.serving.workload import latency_stats
    done = {}
    # ttft values: 0.1, 0.2, 0.3, 0.4; each emits 5 tokens over the 4
    # post-first-token gaps -> tpot 0.1, 0.2, 0.3, 0.4 as well
    for uid, ttft in enumerate([0.1, 0.2, 0.3, 0.4]):
        done[uid] = _stamped_request(
            uid, submitted=10.0, first=10.0 + ttft,
            finished=10.0 + ttft + 4 * ttft, n_out=5)
    stats = latency_stats(done)
    assert stats["ttft_p50_s"] == pytest.approx(0.25)
    assert stats["ttft_p95_s"] == pytest.approx(0.385)
    assert stats["tpot_p50_s"] == pytest.approx(0.25)
    assert stats["tpot_p95_s"] == pytest.approx(0.385)
    # the end-to-end percentiles still cover submit -> finish
    assert stats["p50_s"] == pytest.approx(1.25)


def test_latency_stats_ttft_tpot_exclude_non_ok():
    """Failed/timed-out requests must not leak into the TTFT/TPOT
    percentiles (same exclusion contract as p50/p95), and the ValueError
    semantics are unchanged for empty / all-non-ok inputs."""
    from repro.serving.workload import latency_stats
    done = {}
    for uid, ttft in enumerate([0.1, 0.2, 0.3, 0.4]):
        done[uid] = _stamped_request(
            uid, submitted=10.0, first=10.0 + ttft,
            finished=10.0 + ttft + 4 * ttft, n_out=5)
    done[90] = _stamped_request(90, first=40.0, finished=50.0, n_out=5,
                                status="timed_out")
    done[91] = _stamped_request(91, first=30.0, finished=60.0, n_out=5,
                                status="failed")
    stats = latency_stats(done)
    assert stats["ttft_p50_s"] == pytest.approx(0.25)   # unmoved
    assert stats["tpot_p95_s"] == pytest.approx(0.385)  # unmoved
    assert stats["failed_requests"] == 1
    assert stats["timed_out_requests"] == 1
    with pytest.raises(ValueError, match="finished request"):
        latency_stats({})
    bad = {u: r for u, r in done.items() if r.status != "ok"}
    with pytest.raises(ValueError, match="status"):
        latency_stats(bad)


def test_latency_stats_omits_unavailable_splits():
    """No silent 0.0: requests without a first_token stamp (recorded
    before the stamp existed) contribute no TTFT sample, and 0/1-token
    outputs contribute no TPOT sample — when NO ok request qualifies the
    keys are omitted entirely."""
    from repro.serving.workload import latency_stats
    # no stamps at all -> neither split reported
    done = {0: _stamped_request(0, n_out=3), 1: _stamped_request(1, n_out=3)}
    stats = latency_stats(done)
    assert "ttft_p50_s" not in stats and "tpot_p50_s" not in stats
    assert stats["ok_requests"] == 2
    # stamped but single-token: TTFT reported, TPOT undefined (no
    # inter-token gap exists)
    done = {0: _stamped_request(0, first=10.25, finished=10.25, n_out=1)}
    stats = latency_stats(done)
    assert stats["ttft_p50_s"] == pytest.approx(0.25)
    assert "tpot_p50_s" not in stats and "tpot_p95_s" not in stats
