"""Chip readings of configurations added after the limit check was written.

`test_chipbench_check.test_chip_readings_against_the_limits` puts the
`calibrate.py` readings of every configuration in `BENCHMARK.json` through the
decision a run makes; `recorded()` reads them from `data/limit_readings.jsonl`.
A configuration added later keeps its readings in a file of its own,
`data/limit_readings_<configuration>.jsonl`, and the check reads every such
file with the first."""
import json

import pytest

from chipbench.tests import tiny


def every_reading() -> list:
    """calibrate.py's per-seed lines from the chip, in every readings file."""
    files = [tiny.DATA / "limit_readings.jsonl"] + sorted(
        tiny.DATA.glob("limit_readings_*.jsonl"))
    return [json.loads(x) for f in files for x in f.read_text().splitlines()
            if x.strip()]


@pytest.fixture(autouse=True)
def _every_readings_file(request, monkeypatch):
    if request.module.__name__.endswith("test_chipbench_check"):
        monkeypatch.setattr(request.module, "recorded", every_reading)
