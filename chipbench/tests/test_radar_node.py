"""CPU rehearsals of the ``radar_node`` driver at the ``radar`` tests'
tiny size: the check on worker processes, planted faults in the worker
processes, the workers' memory slots, and the device-owner readers."""

import copy
import os
import threading
import types

import pytest

from conftest import BENCH, CPU, load

from repro.tracks.segments import SegmentProcessor  # noqa: E402

SEED = 2 ** 31 + 43

#: Rows per store block, as in ``test_radar.py``.
BLOCK = 256


def tiny_radar_node(workers: int = 3, tasks_per_message: int = 8,
                    slot_bytes=None):
    """``radar.node`` at three shards of 1,200 points (about 28 ids) on
    ``workers`` worker processes, with the cell's own limits."""
    from chipbench import run
    cfg = copy.deepcopy(load("configs", "radar_node_nppn8"))
    cfg["deployment"]["shard_points"] = 1200
    rt = cfg["deployment"]["runtime"]
    rt["workers"] = workers
    rt["tasks_per_message"] = tasks_per_message
    if slot_bytes is not None:
        cfg["deployment"]["memory_slot_bytes"] = slot_bytes
    traffic = {"driver": "radar_node", "rate_metric": "process_obs_per_s",
               "cut": {"shards": 3}}
    return run.Cell("test.radar.node", cfg, traffic,
                    load("limits", "radar.node"),
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


class FaultyProcessor(SegmentProcessor):
    """The worker function with a fault planted where the worker
    processes run it: each message's results are broken in the worker,
    as ``test_radar.py`` breaks them in its threads."""

    def __init__(self, fault: str, **kwargs):
        super().__init__(**kwargs)
        self.fault = fault

    def process_batch(self, tasks):
        out = super().process_batch(tasks)
        keys = sorted(k for k, ps in out.items() if len(ps.count))
        if self.fault == "half_batch":
            for k in keys[::2]:
                del out[k]
        elif self.fault == "answer_altered":
            out[keys[0]].lat[0, 0] += 1e-3
        elif self.fault == "state_unchanged":
            for ps in out.values():
                for p in ("lat", "lon", "alt_msl_m", "alt_agl_m"):
                    getattr(ps, p)[:] = 0.0
        return out


def test_rehearsal_is_correct():
    from chipbench import run
    res = run.execute(tiny_radar_node(), SEED, 0.01, False, CPU)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "process_obs_per_s"}


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "state_unchanged"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from chipbench import run
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    cell = tiny_radar_node()
    monkeypatch.setattr(
        cell.driver, "make_processor",
        lambda: FaultyProcessor(fault, dem=SyntheticGlobeDEM(),
                                aerodromes=synthetic_aerodromes(n=64)))
    res = run.execute(cell, SEED, 0.01, False, CPU)
    assert not res["correct"], res["checks"]


def test_worker_processes_equal_threads_bit_for_bit():
    """At the ``radar`` tests' size, 3 worker processes and the device
    owner give the ``radar`` driver's planes (8 worker threads) bit for
    bit, every id done exactly once."""
    import numpy as np
    from chipbench import run
    from test_radar import tiny_radar
    node = tiny_radar_node()
    root = os.path.join(run.WORK, node.name)
    tracks, store_dir, _m, _ = run.build_data(node, SEED, root)
    threads = tiny_radar()
    drv = threads.driver.Driver(threads.config, threads.traffic, root,
                                store_dir)
    try:
        want = drv.run_pass().outputs
    finally:
        threads.driver._slot.stop()
        threads.driver._slot = None
    drv = node.driver.Driver(node.config, node.traffic, root, store_dir)
    try:
        got = drv.run_pass()
    finally:
        node.driver.close()
    assert got.tasks == len(tracks.ids) == len(got.outputs) == len(want)
    assert set(got.outputs) == set(tracks.ids) and got.failed == 0
    for tid, a in want.items():
        b = got.outputs[tid]
        assert b.icao24 == a.icao24 and b.airspace == a.airspace
        for p in ("times", "lat", "lon", "alt_msl_m", "alt_agl_m",
                  "vrate_ms", "gspeed_ms", "heading_rad", "turn_rad_s",
                  "count"):
            np.testing.assert_array_equal(getattr(b, p), getattr(a, p))


def test_readers_read_the_workers_and_the_owners_spans():
    """A traced pass on the worker processes, each within its slot: the
    radar readers and the device-owner readers read the workers' and the
    owner's spans."""
    from chipbench import run
    from repro.obs import Tracer
    from repro.runtime.transports import OWNER_TRACK
    cell = tiny_radar_node(tasks_per_message=12)
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, manifest, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    try:
        p = drv.run_pass(tr)
        pids = set(cell.driver._pool.pids.values())
    finally:
        peaks = cell.driver.close()
    assert len(pids) == 3 and os.getpid() not in pids
    assert set(peaks) == {"w0", "w1", "w2"}
    assert all(0 < v < 3_000_000_000 for v in peaks.values())
    assert len(p.outputs) == len(manifest.tracks) and p.failed == 0
    decodes = [e for e in tr.events if e[2] == "store_decode"]
    assert sum(e[6]["obs"] for e in decodes) == manifest.n_points
    assert {e[4] for e in decodes} <= {"w0", "w1", "w2"}
    served = [e for e in tr.events if e[2] == "device.serve"]
    assert served and {e[4] for e in served} == {OWNER_TRACK}
    fake = types.SimpleNamespace(events=tr.events, passes=[p], workers=3)
    for name in ("decode_amplification.radar", "store_decode_share.radar",
                 "store_units_per_kobs.radar", "worker_busy_share.process",
                 "device_queue_share.node", "ipc_share.node",
                 "ipc_mb_per_kobs.node"):
        value = reader(name).read(fake)
        assert value is not None and value >= 0.0, name
    assert 0.0 < reader("ipc_share.node").read(fake) <= 100.0


def _span(name, ts, dur, task, extra=None, track="w0"):
    return (ts, dur, name, "task", track, task, extra)


def test_device_owner_readers_on_hand_built_events():
    """Two messages: exec 4 s and 6 s, worker round trips 1.0 s and
    1.5 s, owner calls 0.4 s and 0.6 s with 0.1 s and 0.3 s in the queue,
    10 MB and 20 MB moved for 5,000 served observations."""
    a, b = "2015_ATL_01-09_a", "2015_ATL_01-09_b"
    events = [
        _span("exec", 0.0, 4.0, a), _span("exec", 0.0, 6.0, b),
        _span("store_decode", 0.0, 1.0, a, {"obs": 2000, "blocks": 3}),
        _span("store_decode", 0.0, 1.0, b, {"obs": 3000, "blocks": 4}),
        _span("segments.device", 1.0, 1.0, a, {"valid": 5, "allocated": 8}),
        _span("segments.device", 1.0, 1.5, b, {"valid": 6, "allocated": 8}),
        _span("device.serve", 1.2, 0.4, a,
              {"queue_s": 0.1, "bytes_in": 2_000_000,
               "bytes_out": 8_000_000}, track="device-owner"),
        _span("device.serve", 1.3, 0.6, b,
              {"queue_s": 0.3, "bytes_in": 4_000_000,
               "bytes_out": 16_000_000}, track="device-owner"),
        # another cell's task and an instant: not read
        _span("device.serve", 1.3, 9.0, "store/s00000",
              {"queue_s": 9.0, "bytes_in": 9, "bytes_out": 9}),
        (0.5, -1.0, "queued", "sched", "manager", a, None),
    ]
    run = types.SimpleNamespace(events=events, passes=[None])
    assert reader("device_queue_share.node").read(run) == \
        pytest.approx(100.0 * 0.4 / 2.5)
    assert reader("ipc_share.node").read(run) == \
        pytest.approx(100.0 * (2.5 - 1.0) / 10.0)
    assert reader("ipc_mb_per_kobs.node").read(run) == \
        pytest.approx(30.0 / 5.0)
    bare = types.SimpleNamespace(
        events=[e for e in events if e[2] != "device.serve"], passes=[None])
    for name in ("device_queue_share.node", "ipc_share.node",
                 "ipc_mb_per_kobs.node"):
        assert reader(name).read(bare) is None
        assert reader(name).read(
            types.SimpleNamespace(events=[], passes=[None])) is None


def _node_module():
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "drivers", "radar_node.py"))


def test_worker_slots_end_the_run_past_a_slot():
    """The watchdog reads each worker's resident memory (stubbed here)
    and, once one passes the slot, ends the run with one line that names
    the worker, its memory, the slot and the pass."""
    mod = _node_module()
    pool = types.SimpleNamespace(worker_ids=["w0", "w1"],
                                 pids={"w0": 11, "w1": 12})
    rss = {11: iter([500, 600, 700]), 12: iter([800, 900, 1001])}
    ended = threading.Event()
    lines = []

    def end(line):
        lines.append(line)
        ended.set()

    slots = mod.WorkerSlots(1000, pool, lambda: 2,
                            read=lambda pid: next(rss[pid], 1001), end=end,
                            period_s=0.001)
    assert ended.wait(5.0)
    peaks = slots.stop()
    assert peaks["w1"] == 1001 and 0 < peaks["w0"] <= 700
    (line,) = lines
    assert "worker process w1 (pid 12) holds 1001" in line
    assert "slot of 1000 bytes" in line and "pass 2 (timed)" in line


def test_worker_slots_within_their_size_end_nothing():
    mod = _node_module()
    pool = types.SimpleNamespace(worker_ids=["w0"], pids={"w0": 11})
    lines = []
    slots = mod.WorkerSlots(1000, pool, lambda: 0, read=lambda pid: 999,
                            end=lines.append, period_s=0.001)
    threading.Event().wait(0.05)
    assert slots.stop() == {"w0": 999}
    assert lines == []


def test_new_cells_load_from_the_benchmark():
    from chipbench import run
    node = run.Cell.load("radar.node")
    assert node.driver.WorkerPool is not None
    assert node.config["deployment"]["runtime"]["backend"] == "processes"
    assert node.limits == load("limits", "radar.process")
    names = {m["name"] for m in node.per_layer}
    assert {"device_queue_share.node", "ipc_share.node",
            "ipc_mb_per_kobs.node", "store_decode_share.radar",
            "worker_busy_share.process",
            "segment_pipeline_roofline"} <= names
    aero = run.Cell.load("aerodrome.process")
    assert aero.traffic["driver"] == "process"
    assert "store_decode_share.process" in {m["name"] for m in aero.per_layer}
