"""The reader of ``store_units_per_kobs.radar``: a known answer from
hand-made events, ``None`` where the decodes carry no ``units`` (a
program without the counter), and the counters of one CPU rehearsal pass
of the radar driver."""

import os
import types

import pytest

from conftest import BENCH
from test_radar import BLOCK, SEED, tiny_radar

NAME = "store_units_per_kobs.radar"


def reader():
    from chipbench import run
    return run.load_module(os.path.join(BENCH, "metrics", NAME + ".py"))


def fake_run(events):
    return types.SimpleNamespace(events=events, passes=[None])


def _decode(ts, task, extra):
    return (ts, 1.0, "store_decode", "task", "w0", task, extra)


#: Two radar messages' decodes (units over points: 9 / 3,000), one
#: decode of another cell's task and one under no task, which count for
#: nothing.
EVENTS = [
    (0.0, 4.0, "exec", "task", "w0", "2015_ATL_a", None),
    _decode(0.0, "2015_ATL_a", {"units": 7, "obs": 2000, "blocks": 6}),
    _decode(1.0, "2015_ATL_b", {"units": 2, "obs": 1000, "blocks": 1}),
    _decode(2.0, "store/s0", {"units": 50, "obs": 10}),
    (3.0, 1.0, "store_decode", "store", "s0", None, {"units": 40,
                                                      "obs": 1}),
]


def test_known_answer():
    assert reader().read(fake_run(EVENTS)) == pytest.approx(
        1000.0 * 9 / 3000)


def test_without_units_reads_none():
    bare = [e if e[2] != "store_decode"
            else e[:6] + ({k: v for k, v in e[6].items() if k != "units"},)
            for e in EVENTS]
    assert reader().read(fake_run(bare)) is None
    assert reader().read(fake_run([])) is None


@pytest.fixture
def _small_blocks(monkeypatch):
    from repro.store import writer
    monkeypatch.setattr(writer, "BLOCK_POINTS", BLOCK)


def test_rehearsal_pass_counts_one_unit_per_row_group(_small_blocks):
    """Each message's decode of a shard inflates its ids' row-group
    units and the one ``offsets`` block: ``units`` is ``blocks`` + 1,
    and the reader reads 1,000 x units / points served."""
    from chipbench import run
    from repro.obs import Tracer
    cell = tiny_radar(tasks_per_message=12)
    root = os.path.join(run.WORK, cell.name)
    _tracks, store_dir, _manifest, _ = run.build_data(cell, SEED, root)
    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    tr = Tracer()
    try:
        drv.run_pass(tr)
    finally:
        cell.driver._slot.stop()
        cell.driver._slot = None
    decodes = [e[6] for e in tr.events if e[2] == "store_decode"]
    assert decodes
    for extra in decodes:
        assert extra["units"] == extra["blocks"] + 1
    got = reader().read(fake_run(tr.events))
    assert got == pytest.approx(1000.0 * sum(e["units"] for e in decodes)
                                / sum(e["obs"] for e in decodes))
