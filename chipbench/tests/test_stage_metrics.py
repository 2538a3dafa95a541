"""The readers of the program's stage spans and counters.

Each reader gets hand-made events with a known answer, and ``None``
where its spans are absent (a program without them); then the events
of one CPU rehearsal pass of each driver.
"""

import os
import types

import pytest

from conftest import BENCH, tiny_cell

SEED = 2 ** 31 + 29

PROCESS = ("store_decode_share.process", "host_pack_share.process",
           "device_wait_share.process", "padded_share.process",
           "worker_cpu_s_per_pass.process")
SCREEN = ("reread_share.screen", "shard_decodes_per_task.screen")


def reader(name):
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def fake_run(events, passes=2):
    return types.SimpleNamespace(events=events, passes=[None] * passes)


def _exec(ts, dur, task, cpu=None, track="w0"):
    return (ts, dur, "exec", "task", track, task,
            None if cpu is None else {"worker_cpu": cpu})


def _stage(ts, dur, name, task, extra=None, track="w0"):
    return (ts, dur, name, "task", track, task, extra or {})


#: Two shard tasks of 10 s and 6 s (one batched message and one alone),
#: a plan decode outside any task, and instants.
PROCESS_EVENTS = [
    (0.0, -1.0, "assigned", "task", "w0", "store/s0", 0),
    _exec(0.0, 5.0, "store/s0", cpu=4.0),
    _exec(5.0, 5.0, "store/s1", cpu=4.5),
    _stage(0.0, 1.0, "store_decode", "store/s0",
           {"bytes": 10, "obs": 5, "tasks": ("store/s0", "store/s1")}),
    _stage(1.0, 2.0, "segments.records", "store/s0"),
    _stage(3.0, 1.0, "segments.pack", "store/s0", {"rows": 3, "width": 128}),
    _stage(4.0, 3.0, "segments.device", "store/s0",
           {"valid": 300, "allocated": 512}),
    _stage(7.0, 1.0, "segments.reassemble", "store/s0"),
    _exec(20.0, 6.0, "store/s2", cpu=1.5, track="w1"),
    _stage(20.0, 1.0, "store_decode", "store/s2", {"bytes": 4, "obs": 2},
           track="w1"),
    _stage(21.0, 3.0, "segments.device", "store/s2",
           {"valid": 100, "allocated": 512}, track="w1"),
    _stage(24.0, 2.0, "segments.pack", "store/s2", track="w1"),
    (30.0, 9.0, "store_decode", "store", "s9", None, {"bytes": 1}),
]

PROCESS_WANT = {
    "store_decode_share.process": 100.0 * 2.0 / 16.0,
    "host_pack_share.process": 100.0 * 6.0 / 16.0,
    "device_wait_share.process": 100.0 * 6.0 / 16.0,
    "padded_share.process": 100.0 * (1024 - 400) / 1024,
    "worker_cpu_s_per_pass.process": 10.0 / 2,
}

#: Two cell tasks (4 s, 2 s) with three member reads, and the plan's
#: spans, which carry no task id.
SCREEN_EVENTS = [
    _exec(0.0, 4.0, "screen/c1/g1", cpu=3.9),
    _stage(0.0, 0.5, "store_decode", "screen/c1/g1"),
    _stage(0.5, 1.0, "segments.device", "screen/c1/g1"),
    _stage(1.5, 0.5, "store_decode", "screen/c1/g1"),
    _stage(2.0, 0.5, "segments.pack", "screen/c1/g1"),
    _stage(2.5, 1.0, "screen.kernel", "screen/c1/g1"),
    _exec(4.0, 2.0, "screen/c2/g1", track="w1"),
    _stage(4.0, 1.0, "store_decode", "screen/c2/g1", track="w1"),
    _stage(5.0, 0.5, "screen.rows", "screen/c2/g1", track="w1"),
    (9.0, 3.0, "store_decode", "store", "s0", None, {}),
    (12.0, 1.0, "screen.plan.bin", "task", "MainThread", None, {}),
]

SCREEN_WANT = {
    "reread_share.screen": 100.0 * 3.5 / 6.0,
    "shard_decodes_per_task.screen": 3 / 2,
}


@pytest.mark.parametrize("name", PROCESS + SCREEN)
def test_reader_known_answer(name):
    events, want = ((PROCESS_EVENTS, PROCESS_WANT[name]) if name in PROCESS
                    else (SCREEN_EVENTS, SCREEN_WANT[name]))
    assert reader(name).read(fake_run(events)) == pytest.approx(want)


#: What a program without stage spans puts in the ring: exec spans
#: without counters, a decode under no task.
BARE = [_exec(0.0, 5.0, "store/s0"), _exec(5.0, 1.0, "screen/c1/g1"),
        (9.0, 3.0, "store_decode", "store", "s0", None, 11)]


@pytest.mark.parametrize("name", PROCESS + SCREEN)
def test_reader_without_its_spans_reads_none(name):
    assert reader(name).read(fake_run(BARE)) is None
    assert reader(name).read(fake_run([])) is None


@pytest.mark.parametrize("which,names", [("mondays.process", PROCESS),
                                         ("mondays.screen", SCREEN)])
def test_readers_on_a_rehearsal_pass(which, names):
    from chipbench import run
    from repro.obs import Tracer
    cell = tiny_cell(which)
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, _manifest, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    drv.run_pass(tr)
    assert tr.dropped == 0
    got = {n: reader(n).read(fake_run(tr.events, passes=1)) for n in names}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    for n in names:
        if n.endswith("_share.process") or n.endswith("_share.screen"):
            assert got[n] <= 100.0, got
