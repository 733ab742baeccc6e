"""What a metric reader is given, and the contract's result line.

Every metric, end to end or per layer, is read by `chipbench/metrics/<name>.py`,
whose `read(ctx)` returns a number, or None where the run holds nothing to read
(the harness then leaves the metric out of the line).
"""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Ctx:
    cfg: dict                  # the configuration file
    mix: dict                  # the traffic file
    peaks: dict                # published peaks of the device kind
    setup_s: float
    window: tuple              # (start, end) on the host clock
    seen: list                 # drivers.Seen of every request sent
    steps: list                # drivers.StepRecord of every step
    drain_end: float = None    # open loop: when the drain stopped
    trace: object = None       # trace.Summary of a traced run
    traced_steps: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def due_in_window(self):
        return [s for s in self.seen if s.due < self.window[1]]


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def fmt_check(check: dict) -> str:
    return " ".join(f"{k}={v['value']!r} limit={v['limit']!r}"
                    for k, v in check.items())


def emit(result: dict, info: dict):
    """Print the run's record line, the check on stderr, and the result as
    the last line of stdout (its `check` key last)."""
    print(json.dumps(info), flush=True)
    check = result.pop("check")
    result["check"] = check
    print("check: " + fmt_check(check), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

