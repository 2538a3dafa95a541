"""The fused segment pipeline's share of its roofline.

The least time the chip could take for the pipeline work of the traced
passes -- the larger of required operations over peak FLOP/s and
required bytes over peak bandwidth (``chipbench.work.pipeline``: valid
knots and resampled points, not padded shapes) -- over the device time
of the fused programs in the trace.  The bytes bound applies.
"""

from chipbench import work

#: The fused pipeline (``segment_pipeline._pipeline``, jitted from a
#: ``functools.partial``, so XLA names its module ``jit__unknown``): the
#: programs that run its ``track_interp`` Pallas kernel.
MODULE = r"^jit__unknown$"
HAS = "%track_interp_pallas"


def read(run):
    device_s = run.device_seconds(MODULE, has=HAS)
    if device_s <= 0.0:
        return None
    flops, nbytes = run.required("pipeline")
    n = len(run.passes)
    return work.roofline_share(flops * n, nbytes * n, device_s,
                               run.device_kind)
