"""CPU rehearsals of every driver, the control and the planted faults.

Each test drives ``run.execute`` -- set-up, warm-up, window, check --
past the harness's look for a chip, on a tiny cell.
"""

import numpy as np
import pytest

from conftest import CELLS, CPU, tiny_cell

SEED = 2 ** 31 + 17


@pytest.mark.parametrize("which", CELLS)
def test_rehearsal_is_correct(which):
    from chipbench import run
    res = run.execute(tiny_cell(which), SEED, 0.01, False, CPU)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s",
                                   tiny_cell(which).traffic["rate_metric"]}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("which", CELLS)
def test_control_fails(which):
    """The reference in bfloat16, in the program's place, is not correct:
    some number lies above its limit."""
    from chipbench import control
    cell = tiny_cell(which)
    out = control.readings(cell, [SEED, SEED + 1], CPU)
    lim = cell.limits["limits"]
    assert all(out["lower"][k] <= lim[k] for k in lim), out["lower"]
    assert any(not out["upper"][k] <= lim[k] for k in lim), out["upper"]


# ---------------------------------------------------------------------------
# faults planted under the timed path
# ---------------------------------------------------------------------------

def _process_fault(monkeypatch, fault):
    from repro.tracks import segments
    orig = segments.SegmentProcessor.process_batch

    def broken(self, tasks):
        out = orig(self, tasks)
        for doc in out.values():
            keys = sorted(doc)
            if fault == "half_batch":
                for k in keys[::2]:
                    del doc[k]
            elif fault == "answer_altered":
                doc[keys[0]].lat[0, 0] += 1e-3
            elif fault == "state_unchanged":
                for ps in doc.values():
                    for p in ("lat", "lon", "alt_msl_m", "alt_agl_m"):
                        getattr(ps, p)[:] = 0.0
        return out

    monkeypatch.setattr(segments.SegmentProcessor, "process_batch", broken)


def _screen_fault(monkeypatch, fault):
    from repro.tracks import workflow
    orig = workflow.ScreenWorker.__call__

    def broken(self, task):
        doc = orig(self, task)
        if fault == "half_batch":
            doc["candidates"] = doc["candidates"][1::2]
        elif fault == "answer_altered":
            for c in doc["candidates"]:
                c["h_m"] += 20.0
        elif fault == "state_unchanged":
            doc["candidates"] = []
        return doc

    monkeypatch.setattr(workflow.ScreenWorker, "__call__", broken)


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "state_unchanged"])
@pytest.mark.parametrize("which", CELLS)
def test_planted_fault_is_not_correct(which, fault, monkeypatch):
    from chipbench import run
    cell = tiny_cell(which)
    if cell.traffic["driver"] == "process":
        _process_fault(monkeypatch, fault)
    else:
        _screen_fault(monkeypatch, fault)
    res = run.execute(cell, SEED, 0.01, False, CPU)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("which", ["aerodrome.screen", "mondays.screen"])
def test_screen_cells_hold_pairs(which):
    """The tiny screen cells are not vacuous: the reference finds pairs
    there, so the faults above have candidates to lose."""
    from chipbench import reference
    cell = tiny_cell(which)
    from chipbench import gen
    tr = gen.make_tracks(cell.config, cell.traffic, SEED)
    segs = reference.segment_planes(tr, reference.Terrain(),
                                    reference.aerodromes())
    pairs = reference.screen_pairs(reference.row_ids(tr, segs), segs,
                                   926.0, 152.4, 6.0, 0.1)
    assert sum(np.isfinite(p.h_in) for p in pairs.values()) >= 5
