"""Fused-pipeline calls per pass: one per (bucket width, AGL variant,
row count) batch that ``SegmentProcessor`` sends to the device.

Source: the program's counters, ``compile_hits + compile_misses`` of
``repro.kernels.ops.get_pipeline_stats()``, over the window, per pass.
"""


def read(run):
    if not run.passes:
        return None
    return run.pipeline_calls / len(run.passes)
