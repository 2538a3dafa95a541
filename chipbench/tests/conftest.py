"""Tests of the benchmark itself, on the CPU at tiny sizes.

    python -m pytest chipbench/tests

(The repository's ``pytest.ini`` collects ``tests/`` only.)
"""

import copy
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def tiny_cell(which: str):
    """A cell of each driver and configuration at a size the CPU (Pallas
    in interpret mode) runs in seconds; the limits are the cell's own."""
    from chipbench import run
    if which == "mondays.process":
        cfg = copy.deepcopy(load("configs", "mondays_enroute"))
        cfg["deployment"]["shard_points"] = 700
        traffic = {"driver": "process", "rate_metric": "process_obs_per_s",
                   "cut": {"shards": 2}}
    elif which == "aerodrome.process":
        cfg = copy.deepcopy(load("configs", "aerodrome_terminal"))
        cfg["generator"]["aircraft_per_hour"] = 40
        cfg["deployment"]["shard_points"] = 900
        traffic = {"driver": "process", "rate_metric": "process_obs_per_s",
                   "cut": {"shards": 2}}
    elif which == "aerodrome.screen":
        cfg = copy.deepcopy(load("configs", "aerodrome_terminal"))
        cfg["generator"]["aircraft_per_hour"] = 1200
        traffic = {"driver": "screen", "rate_metric": "screen_obs_per_s",
                   "cut": {"hotspots": 1}}
    elif which == "mondays.screen":
        cfg = copy.deepcopy(load("configs", "mondays_enroute"))
        cfg["generator"]["tracks_per_deg2"] = 400.0
        traffic = {"driver": "screen", "rate_metric": "screen_obs_per_s",
                   "cut": {"box_deg": [35.0, 35.5, -100.0, -99.5]}}
    else:
        raise KeyError(which)
    return run.Cell("test." + which, cfg, traffic, load("limits", which),
                    end_to_end=[{"name": traffic["rate_metric"],
                                 "unit": "obs/s"},
                                {"name": "setup_s", "unit": "s"}])


CELLS = ("mondays.process", "aerodrome.process", "aerodrome.screen",
         "mondays.screen")


@pytest.fixture(autouse=True)
def _work_dir(tmp_path, monkeypatch):
    """Runs write under a temporary directory, not the checkout."""
    from chipbench import run
    monkeypatch.setattr(run, "WORK", str(tmp_path / "work"))
