"""CPU rehearsals of the ``radar`` driver at a tiny size: the store's
shard plan, the check against the reference, the control, the planted
faults, the memory slot, and the store counters its readers read."""

import copy
import os
import threading
import types

import numpy as np
import pytest

from conftest import BENCH, CPU, load

SEED = 2 ** 31 + 41

#: Rows per store block in these rehearsals: small, so a tiny shard
#: holds several blocks and a message reads only some of them.
BLOCK = 256


def tiny_radar(tasks_per_message: int = 8, slot_bytes=None):
    """``radar.process`` at three shards of 1,200 points (about 28 ids),
    with the cell's own limits."""
    from chipbench import run
    cfg = copy.deepcopy(load("configs", "radar_terminal"))
    cfg["deployment"]["shard_points"] = 1200
    cfg["deployment"]["runtime"]["tasks_per_message"] = tasks_per_message
    if slot_bytes is not None:
        cfg["deployment"]["memory_slot_bytes"] = slot_bytes
    traffic = {"driver": "radar", "rate_metric": "process_obs_per_s",
               "cut": {"shards": 3}}
    return run.Cell("test.radar.process", cfg, traffic,
                    load("limits", "radar.process"),
                    end_to_end=[{"name": "process_obs_per_s",
                                 "unit": "obs/s"},
                                {"name": "setup_s", "unit": "s"}])


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    from repro.store import writer
    monkeypatch.setattr(writer, "BLOCK_POINTS", BLOCK)


def reader(name):
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_shard_plan_is_the_first_radars_ids():
    """The cut fills exactly its shards with ids of the first radar in id
    order, named by the deployment's layout, and check_store holds it to
    that shard count."""
    from chipbench import gen, run
    cell = tiny_radar()
    root = os.path.join(run.WORK, cell.name)
    tracks, _store, manifest, _ = run.build_data(cell, SEED, root)
    assert len(manifest.shards) == 3
    assert [t.track_id for t in manifest.tracks] == tracks.ids
    assert all(i.startswith("2015_ATL_01-09_") for i in tracks.ids)
    assert tracks.ids == sorted(tracks.ids)
    n = np.diff(tracks.offsets)
    assert n.min() >= 26 and n.max() <= 1800 / 4.8 + 1 + 1
    # one icao24 per id, times increasing within each id
    icao = tracks.cols["icao24"][tracks.offsets[:-1]]
    assert len(set(icao)) == len(tracks)
    step = np.diff(tracks.cols["time"])
    inner = np.ones(len(step), bool)
    inner[tracks.offsets[1:-1] - 1] = False
    assert (step[inner] > 0).all()
    wrong = copy.deepcopy(cell.traffic)
    wrong["cut"] = {"shards": 2}
    with pytest.raises(RuntimeError):
        gen.check_store(cell.config, wrong, manifest)


def test_rehearsal_is_correct():
    from chipbench import run
    res = run.execute(tiny_radar(), SEED, 0.01, False, CPU)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "process_obs_per_s"}


def test_control_fails():
    from chipbench import control
    cell = tiny_radar()
    out = control.readings(cell, [SEED, SEED + 1], CPU)
    lim = cell.limits["limits"]
    assert all(out["lower"][k] <= lim[k] for k in lim), out["lower"]
    assert any(not out["upper"][k] <= lim[k] for k in lim), out["upper"]


def _radar_fault(monkeypatch, fault):
    """Break each message's results: one task per id, so the results
    are ProcessedSegments keyed by id."""
    from repro.tracks import segments
    orig = segments.SegmentProcessor.process_batch

    def broken(self, tasks):
        out = orig(self, tasks)
        keys = sorted(k for k, ps in out.items() if len(ps.count))
        if fault == "half_batch":
            for k in keys[::2]:
                del out[k]
        elif fault == "answer_altered":
            out[keys[0]].lat[0, 0] += 1e-3
        elif fault == "state_unchanged":
            for ps in out.values():
                for p in ("lat", "lon", "alt_msl_m", "alt_agl_m"):
                    getattr(ps, p)[:] = 0.0
        return out

    monkeypatch.setattr(segments.SegmentProcessor, "process_batch", broken)


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "state_unchanged"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from chipbench import run
    _radar_fault(monkeypatch, fault)
    res = run.execute(tiny_radar(), SEED, 0.01, False, CPU)
    assert not res["correct"], res["checks"]


def _slot_module():
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "drivers", "radar.py"))


def test_slot_ends_the_run_past_its_size():
    """The watchdog reads the resident memory (stubbed here) and, once
    the growth passes the slot, ends the run with one line that names
    the slot, the growth and the pass."""
    rss = iter([1000, 1500, 1900, 2101])
    ended = threading.Event()
    lines = []

    def end(line):
        lines.append(line)
        ended.set()

    slot = _slot_module().Slot(1000, lambda: 0, read=lambda: next(rss, 2101),
                               end=end, period_s=0.001)
    assert slot.base == 1000
    assert ended.wait(5.0)
    assert slot.stop() == 1101
    (line,) = lines
    assert "slot of 1000 bytes" in line and "grew 1101 bytes" in line
    assert "pass 0 (the warm-up)" in line


def test_slot_within_its_size_ends_nothing():
    lines = []
    slot = _slot_module().Slot(1000, lambda: 2, read=lambda: 1500,
                               end=lines.append, period_s=0.001)
    threading.Event().wait(0.05)
    assert slot.stop() == 0 and slot.base == 1500
    assert lines == []


def test_driver_holds_the_run_to_the_configured_slot(monkeypatch):
    """A driver built with a slot of one byte ends the run in its first
    pass: the exit is stubbed, and the resident memory is read from a
    counter that grows by a byte a reading."""
    from chipbench import run
    cell = tiny_radar(slot_bytes=1)
    drv_mod = cell.driver
    grown = iter(range(10 ** 9))
    ended = threading.Event()
    lines = []
    monkeypatch.setattr(drv_mod, "rss_bytes", lambda: next(grown))
    monkeypatch.setattr(drv_mod, "end_run",
                        lambda line: (lines.append(line), ended.set()))
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, _m, _ = run.build_data(cell, SEED, root)
    drv = drv_mod.Driver(cell.config, cell.traffic, root, store_dir)
    try:
        assert ended.wait(5.0)
    finally:
        drv_mod._slot.stop()
        drv_mod._slot = None
    assert "slot of 1 bytes" in lines[0] and "pass 0" in lines[0]
    assert drv.passes == 0


def test_message_decodes_carry_block_counters_and_readers_read_them():
    """A traced pass: every store decode of a message carries blocks,
    obs_decoded, obs and bytes, under the message's first id; the radar
    readers read them, and read None on decodes without them."""
    from chipbench import run
    from repro.obs import Tracer
    cell = tiny_radar(tasks_per_message=12)
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, manifest, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    try:
        drv.run_pass(tr)
    finally:
        cell.driver._slot.stop()
        cell.driver._slot = None
    assert tr.dropped == 0
    decodes = [e for e in tr.events if e[2] == "store_decode"]
    assert decodes
    for e in decodes:
        assert {"blocks", "obs_decoded", "obs", "bytes"} <= set(e[6])
        assert e[5].startswith("2015_ATL_")
        assert 0 < e[6]["obs"] <= e[6]["obs_decoded"]
        assert e[6]["blocks"] <= -(-1200 // BLOCK) + 1
    assert sum(e[6]["obs"] for e in decodes) == manifest.n_points
    fake = types.SimpleNamespace(events=tr.events, passes=[None])
    amp = reader("decode_amplification.radar").read(fake)
    share = reader("store_decode_share.radar").read(fake)
    assert amp == pytest.approx(
        sum(e[6]["obs_decoded"] for e in decodes)
        / sum(e[6]["obs"] for e in decodes))
    assert amp >= 1.0 and 0.0 < share <= 100.0
    bare = [e if e[2] != "store_decode"
            else e[:6] + ({"bytes": e[6]["bytes"], "obs": e[6]["obs"]},)
            for e in tr.events]
    fake_bare = types.SimpleNamespace(events=bare, passes=[None])
    assert reader("decode_amplification.radar").read(fake_bare) is None
    assert reader("store_decode_share.radar").read(fake_bare) is None
    empty = types.SimpleNamespace(events=[], passes=[None])
    assert reader("decode_amplification.radar").read(empty) is None
    assert reader("store_decode_share.radar").read(empty) is None


@pytest.mark.parametrize("name", ["padded_share.process",
                                  "store_decode_share.process"])
def test_shard_task_readers_find_nothing_in_radar_messages(name):
    """The shard-task readers read only ``store/`` task ids: a radar
    pass gives them nothing, so they stay off the cell."""
    from chipbench import run
    from repro.obs import Tracer
    cell = tiny_radar()
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, _m, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    try:
        drv.run_pass(tr)
    finally:
        cell.driver._slot.stop()
        cell.driver._slot = None
    fake = types.SimpleNamespace(events=tr.events, passes=[None])
    assert reader(name).read(fake) is None
