"""Share of a screen pass spent outside the cell tasks' job: the plan
(every shard's rows re-derived through the fused pipeline and binned
into the spatial hash) and the candidate file.

Source: the host clock around each pass, less the screen phase's
``PhaseReport.job_seconds``, summed over the window's passes.
"""


def read(run):
    wall = sum(run.walls)
    if wall <= 0.0:
        return None
    return 100.0 * (wall - sum(p.job_s for p in run.passes)) / wall
