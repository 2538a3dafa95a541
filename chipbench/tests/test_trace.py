"""The trace reduction, on hand-made intervals and on a recorded trace.

``data/process_pass.xplane.pb`` is the profiler trace of one
``mondays_enroute`` process pass over one store shard, recorded on one
TPU v5 lite with the options the benchmark uses.
"""

import os

import numpy as np
import pytest

from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps():
    s, e = tr._union(np.array([5.0, 0.0, 1.0, 8.0]),
                     np.array([6.0, 2.0, 3.0, 9.0]))
    assert s.tolist() == [0.0, 5.0, 8.0] and e.tolist() == [3.0, 6.0, 9.0]


def test_reduce_hand_made():
    raw = {
        "devices": [{
            "ops": (np.array([1.0, 1.5, 4.0, 9.5]),
                    np.array([2.0, 3.0, 5.0, 12.0]),
                    ["%track_interp_pallas", "%fusion", "%while",
                     "%track_interp_pallas"]),
            "modules": [("jit__unknown", 1.0, 3.0),
                        ("jit__unknown", 4.0, 5.0),
                        ("jit__unknown", 9.5, 12.0)]}],
        "annotations": [("pass", 0.0, 5.0), ("pass", 5.0, 10.0),
                        ("screen.plan", 5.0, 9.0),
                        ("screen.cells", 9.0, 10.0)],
    }
    red = tr.reduce(raw)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert red["programs"] == [
        ("jit__unknown", 2.0, frozenset({"%track_interp_pallas",
                                         "%fusion"})),
        ("jit__unknown", 1.0, frozenset({"%while"})),
        ("jit__unknown", 0.5, frozenset({"%track_interp_pallas"}))]
    # gaps: 0-1 (pass), 3-4 (pass), 5-9.5 (screen.plan at 7.25)
    assert red["idle_gaps"][0] == ["screen.plan", pytest.approx(4.5)]
    assert sorted(g[1] for g in red["idle_gaps"]) == pytest.approx(
        [1.0, 1.0, 4.5])
    assert red["device_ops"][0] == ["%track_interp_pallas",
                                    pytest.approx(1.5)]


def test_reduce_needs_a_pass():
    with pytest.raises(ValueError):
        tr.reduce({"devices": [{"ops": (np.zeros(0), np.zeros(0), []),
                                "modules": []}], "annotations": []})


def test_names_drop_their_ids():
    assert tr.module_name("jit__unknown(123)") == "jit__unknown"
    assert tr.op_kind("%agl_lookup_pallas.1 = f32[8,128] custom-call(...)"
                      ) == "%agl_lookup_pallas"


def test_recorded_trace():
    raw = tr.read(os.path.join(DATA, "process_pass.xplane.pb"))
    red = tr.reduce(raw)
    assert len(raw["devices"]) == 1
    assert 0.0 < red["busy_s"] < red["window_s"]
    fused = [p for p in red["programs"] if "%track_interp_pallas" in p[2]]
    assert len(fused) == len(red["programs"]) == 7
    assert {"%agl_lookup_pallas", "%dynamic_rates_pallas"} <= fused[0][2]
    # Every operation runs inside a program: the busy union is the
    # programs' time.
    assert red["busy_s"] == pytest.approx(sum(p[1] for p in fused),
                                          rel=1e-3)
    names = {a[0] for a in raw["annotations"]}
    assert {"pass", "process.job"} <= names
    assert red["idle_gaps"] and all(g[1] > 0 for g in red["idle_gaps"])
