"""The reader of member tracks per fused-pipeline call in the screen
cell tasks: hand-made spans of a program that derives a cell's members
one by one (no ``tracks`` counter) and of one that derives them
together, then the events of one CPU rehearsal pass."""

import os
import types

import pytest

from conftest import BENCH, tiny_cell

NAME = "tracks_per_pipeline_call.screen"
SEED = 2 ** 31 + 1919


def reader():
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "metrics", NAME + ".py"))


def fake_run(events):
    return types.SimpleNamespace(events=events, passes=[None])


def _span(ts, dur, name, task, extra=None):
    return (ts, dur, name, "task", "w0", task, extra)


def _device(task, n):
    return [_span(0.1 * k, 0.1, "segments.device", task,
                  {"valid": 10, "allocated": 16}) for k in range(n)]


#: Per-track derivation: a ``screen.rows`` span per member with ``rows``
#: alone, one device call per member.
PER_TRACK = ([_span(0.0, 2.0, "exec", "screen/c1/g1")]
             + _device("screen/c1/g1", 3)
             + [_span(1.0, 0.1, "screen.rows", "screen/c1/g1", {"rows": 2})
                for _ in range(3)])

#: Batched derivation: two cell tasks of 6 and 2 members in 2 and 1
#: device calls; a plan's device call and rows under no task.
BATCHED = ([_span(0.0, 2.0, "exec", "screen/c1/g1"),
            _span(1.5, 0.1, "screen.rows", "screen/c1/g1",
                  {"rows": 9, "tracks": 6}),
            _span(2.0, 1.0, "exec", "screen/c2/g1"),
            _span(2.5, 0.1, "screen.rows", "screen/c2/g1",
                  {"rows": 2, "tracks": 2}),
            _span(5.0, 0.5, "segments.device", None, {"valid": 1}),
            _span(5.5, 0.1, "screen.plan.rows", None, {"rows": 40})]
           + _device("screen/c1/g1", 2) + _device("screen/c2/g1", 1))


@pytest.mark.parametrize("events,want", [(PER_TRACK, None), ([], None),
                                         (BATCHED, 8 / 3)])
def test_reader_known_answer(events, want):
    got = reader().read(fake_run(events))
    assert got == (None if want is None else pytest.approx(want))


def test_reader_on_a_rehearsal_pass():
    from chipbench import run
    from repro.obs import Tracer
    cell = tiny_cell("aerodrome.screen")
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, _manifest, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    drv.run_pass(tr)
    assert tr.dropped == 0
    assert reader().read(fake_run(tr.events)) > 1.0
