"""``screen`` mix: the workflow's barrier screen phase, one pass per call.

Set-up leaves a processed store under ``<root>/store`` and a workflow
checkpoint that records organize, archive, store-build and process as
done, so ``TrackWorkflow(root, input="store", screen=True, n_workers=8,
tasks_per_message=4).run()`` executes exactly the screen phase:

1. the plan: every shard's rows re-derived through the fused pipeline
   and binned into the ``gridhash`` spatial hash;
2. one self-scheduled ``ScreenWorker`` task per multi-row cell, on the
   ``jit`` screen backend that the workflow runs;
3. ``candidates.json``.

Each pass rewrites that checkpoint first.  The candidates of a pass are
what the check compares with the all-pairs reference.
"""

from __future__ import annotations

import dataclasses
import json
import os

from chipbench import reference

#: Phases the set-up stands for: the store exists, processed.
DONE = ["organize", "archive", "store-build", "process"]


@dataclasses.dataclass
class Pass:
    tasks: int           # runtime tasks (cells)
    failed: int
    job_s: float         # the screen phase's run_job wall time
    outputs: list        # candidates.json entries


#: The workflow's private methods that the driver hooks: a renamed one
#: stops the driver rather than silently dropping its annotations.
HOOKS = ("_screen_tasks_full", "_run_phase", "_save_ckpt")


def _workflow_class():
    """``TrackWorkflow`` with the benchmark's profiler annotations
    around the screen phase's plan and its cell tasks, counting the cell
    tasks that ``run_job`` reports failed."""
    import jax
    from repro.tracks.workflow import TrackWorkflow
    gone = [h for h in HOOKS if not callable(getattr(TrackWorkflow, h, None))]
    if gone:
        raise RuntimeError(f"TrackWorkflow has no {', '.join(gone)}: the "
                           f"screen driver hooks the screen phase there")

    class Annotated(TrackWorkflow):
        failed = 0

        def _screen_tasks_full(self):
            with jax.profiler.TraceAnnotation("screen.plan"):
                return super()._screen_tasks_full()

        def _run_phase(self, *a, **kw):
            with jax.profiler.TraceAnnotation("screen.cells"):
                result = super()._run_phase(*a, **kw)
            self.failed += len(result.failures)
            return result

    return Annotated


class Driver:
    """Drives screen passes over the processed store under ``root``."""

    phase = "screen"

    def __init__(self, config: dict, traffic: dict, root: str,
                 store_dir: str):
        dep = config["deployment"]
        rt = dep["runtime"]
        if os.path.abspath(store_dir) != os.path.abspath(
                os.path.join(root, "store")):
            raise ValueError("the screen phase reads <root>/store")
        self.wf = _workflow_class()(
            root, input="store", screen=True, n_workers=rt["workers"],
            tasks_per_message=rt["tasks_per_message"], policy=rt["policy"],
            screen_h_m=dep["screen"]["h_thresh_m"],
            screen_v_m=dep["screen"]["v_thresh_m"],
            screen_cell_deg=dep["grid"]["cell_deg"])

    def run_pass(self, tracer=None) -> Pass:
        wf = self.wf
        wf._save_ckpt({"phases_done": list(DONE), "manager": None})
        wf.reports = []
        wf.tracer = tracer
        wf.failed = 0
        wf.run()
        with open(wf.candidates_path) as f:
            cands = json.load(f)["candidates"]
        rep = [r for r in wf.reports if r.phase == "screen"]
        return Pass(tasks=sum(r.tasks for r in rep), failed=wf.failed,
                    job_s=sum(r.job_seconds for r in rep), outputs=cands)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

class Check:
    """The all-pairs reference over the float64 reference's rows.

    A pair whose separation lies within ``band`` of a threshold may
    fall either way under float32 rounding: the reference screens each
    pair under limits narrowed and widened by the band, and a candidate
    set is right when it holds every narrowed pair and no pair outside
    the widened ones.  The band is twice the plane limits of position
    and altitude, which bound how far the float32 planes may lie from
    the float64 ones.
    """

    def __init__(self, tracks, config: dict, limits: dict):
        sc = config["deployment"]["screen"]
        self.h_m, self.v_m = sc["h_thresh_m"], sc["v_thresh_m"]
        self.dh, self.dv = limits["band"]["h_m"], limits["band"]["v_m"]
        self.tracks = tracks
        self.terrain = reference.Terrain()
        self.aero = reference.aerodromes()
        self.segs = reference.segment_planes(tracks, self.terrain, self.aero)
        self.ids = reference.row_ids(tracks, self.segs)
        self.pairs = reference.screen_pairs(self.ids, self.segs, self.h_m,
                                            self.v_m, self.dh, self.dv)

    def program(self, last: Pass) -> dict:
        return reference.screen_errors(last.outputs, self.pairs)

    def control(self) -> dict:
        low = reference.segment_planes(self.tracks, self.terrain, self.aero,
                                       precision="bfloat16")
        pairs = reference.screen_pairs(reference.row_ids(self.tracks, low),
                                       low, self.h_m, self.v_m, 0.0, 0.0)
        cands = [{"a": a, "b": b, "h_m": p.h_out, "v_m": p.v_out}
                 for (a, b), p in pairs.items()]
        return reference.screen_errors(cands, self.pairs)
