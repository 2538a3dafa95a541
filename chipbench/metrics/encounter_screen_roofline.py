"""The encounter screen program's share of its roofline.

Required work (``chipbench.work.screen``): pairs of rows of different
aircraft whose spans share a second, times the seconds shared, counted
by the reference -- the same whatever implements the screen -- and one
read of each such row.  The screen's float32 math runs on the VPU,
which has no published peak, so against the bf16 peak the operations
bound is far below the bytes bound: the bytes bound applies.
"""

from chipbench import work

#: The screen program (``encounter_screen._jitted``, jitted from a
#: ``functools.partial``, so XLA names its module ``jit__unknown``): the
#: ``jit__unknown`` programs that do not run the fused pipeline's
#: ``track_interp`` kernel, the only other such programs of a screen pass.
MODULE = r"^jit__unknown$"
LACKS = "%track_interp_pallas"


def read(run):
    device_s = run.device_seconds(MODULE, lacks=LACKS)
    if device_s <= 0.0:
        return None
    flops, nbytes = run.required("screen")
    if flops <= 0:
        return None
    n = len(run.passes)
    return work.roofline_share(flops * n, nbytes * n, device_s,
                               run.device_kind)
