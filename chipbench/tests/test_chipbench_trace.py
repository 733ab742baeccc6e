"""The trace reducer on a small trace recorded on a TPU v5e.

`data/decode_small.xplane.pb`: one dense decode step of internlm2-1.8b at 32
lanes (chipbench/configs/internlm2-1.8b.json), traced by the benchmark's own
Tracer, so it holds the `chipbench.window` and `chipbench.step` host spans.
"""
from pathlib import Path

import pytest

from chipbench import trace

SMALL = Path(__file__).resolve().parent / "data" / "decode_small.xplane.pb"


@pytest.fixture(scope="module")
def summ():
    return trace.summarize(str(SMALL))


def test_window_and_busy(summ):
    assert summ.devices == 1
    assert 0.04 < summ.window_s < 0.2
    assert 0 < summ.busy_s <= summ.window_s
    idle = 1 - summ.busy_s / summ.window_s
    assert 0 <= idle < 1


def test_programs_and_kernels(summ):
    seconds, n = summ.modules(r"_decode_greedy")
    assert n == 1 and 0.01 < seconds <= summ.busy_s
    seconds, n = summ.ops(r"^paged_decode(\.\d+)?$")
    assert n == 24                      # one call per layer
    assert 0 < seconds < summ.busy_s
    assert summ.ops(r"dsg_ffn_csr") == (0, 0)     # a dense step


def test_containers_do_not_count_twice(summ):
    assert not any(n.startswith("while") for n in summ.op_seconds)
    assert sum(summ.op_seconds.values()) <= summ.busy_s * 1.001


def test_breakdown_lists(summ):
    ops = summ.top_ops()
    assert 0 < len(ops) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    gaps = summ.top_gaps()
    assert 0 < len(gaps) <= 10
    assert {n for n, _ in gaps} <= {"step", "submit", "sleep", "host"}
    idle = summ.window_s - summ.busy_s
    assert sum(s for _, s in summ.gaps) == pytest.approx(idle, rel=1e-6)


def test_op_name():
    assert trace.op_name("%paged_decode.6 = (bf16[32]) custom-call()") == \
        "paged_decode.6"
