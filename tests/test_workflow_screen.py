"""End-to-end --screen workflow: barrier vs DAG byte-identity + exactness.

One scaled raw feed (single hourly file so the synthetic aircraft share
an hour and actually co-bin), pushed through the full store-input
pipeline twice — barrier mode and streaming-DAG mode — with screening
enabled.  The candidates.json artifacts must be byte-identical, and
their pair set must equal the brute-force all-pairs screen over the
same store-derived rows.
"""

import json
import os

import pytest

from repro.kernels.encounter_screen import brute_force_screen
from repro.tracks.segments import SegmentProcessor, segment_tasks_from_store
from repro.tracks.workflow import TrackWorkflow, _screen_rows_for_uri

# Calibrated so the ~60 co-located synthetic aircraft yield a small,
# non-empty candidate set (3 pairs) in a few screening cells.
SCREEN_KW = dict(
    input="store", store_target_points=2048, screen=True,
    screen_h_m=50_000.0, screen_v_m=1000.0, screen_cell_deg=1.0,
    n_workers=4, poll_interval=0.003)


def _run(root, mode):
    wf = TrackWorkflow(str(root), mode=mode, **SCREEN_KW)
    wf.generate_raw(n_files=1, scale=1e3)
    wf.run()
    return wf


@pytest.fixture(scope="module")
def barrier_wf(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("screen_barrier"), "barrier")


@pytest.fixture(scope="module")
def dag_wf(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("screen_dag"), "dag")


def test_barrier_candidates_artifact(barrier_wf):
    with open(barrier_wf.candidates_path) as f:
        doc = json.load(f)
    assert doc["schema"] == "repro.encounters/v1"
    assert doc["thresholds"] == {"h_m": 50_000.0, "v_m": 1000.0}
    assert doc["grid"]["cell_deg"] == 1.0
    cands = doc["candidates"]
    assert len(cands) >= 1
    # Canonical: a < b, sorted by (a, b), unique pairs.
    pairs = [(c["a"], c["b"]) for c in cands]
    assert all(a < b for a, b in pairs)
    assert pairs == sorted(set(pairs))


def test_dag_byte_identical_to_barrier(barrier_wf, dag_wf):
    with open(barrier_wf.candidates_path, "rb") as f:
        barrier = f.read()
    with open(dag_wf.candidates_path, "rb") as f:
        dag = f.read()
    assert barrier == dag


def test_candidates_equal_brute_force(barrier_wf):
    """The workflow's grid-screened candidates are exactly the brute
    force all-pairs set over the same store-derived rows."""
    proc = SegmentProcessor(backend=barrier_wf.backend)
    rows = []
    for t in segment_tasks_from_store(barrier_wf.store_dir,
                                      granularity="shard"):
        rows.extend(_screen_rows_for_uri(proc, t.payload))
    want = brute_force_screen(rows, config=barrier_wf.screen_config)
    with open(barrier_wf.candidates_path) as f:
        got = json.load(f)["candidates"]
    assert [(c["a"], c["b"]) for c in got] == \
        [(c["a"], c["b"]) for c in want]


def test_screen_resumes_when_artifact_missing(barrier_wf):
    """Deleting candidates.json and re-running only redoes screening
    (phases_done guard drops 'screen' when the artifact is gone)."""
    os.remove(barrier_wf.candidates_path)
    wf = TrackWorkflow(barrier_wf.root, mode="barrier", **SCREEN_KW)
    wf.run()
    assert os.path.exists(barrier_wf.candidates_path)
    test_barrier_candidates_artifact(barrier_wf)


def test_screen_requires_store_input(tmp_path):
    with pytest.raises(ValueError, match="store"):
        TrackWorkflow(str(tmp_path), screen=True, input="zip")


def test_workflow_keeps_benchmark_hooks():
    """The chip benchmark's screen driver subclasses ``TrackWorkflow``:
    it overrides the private methods it names in ``HOOKS`` and builds
    the workflow with keywords.  Read both from the driver's source, so
    renaming a hook or a keyword fails here, on the CPU, and not first
    in a run on the chip."""
    import ast
    import inspect
    path = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "drivers", "screen.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    hooks = next(ast.literal_eval(node.value) for node in ast.walk(tree)
                 if isinstance(node, ast.Assign)
                 and any(getattr(t, "id", None) == "HOOKS"
                         for t in node.targets))
    assert {"_screen_tasks_full", "_run_phase", "_save_ckpt"} <= set(hooks)
    for hook in hooks:
        assert callable(getattr(TrackWorkflow, hook, None)), hook
    calls = [node for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Call)
             and getattr(node.func.func, "id", None) == "_workflow_class"]
    assert len(calls) == 1
    keywords = {kw.arg for kw in calls[0].keywords}
    assert {"input", "screen", "n_workers"} <= keywords
    inspect.signature(TrackWorkflow).bind("root", **dict.fromkeys(keywords))
