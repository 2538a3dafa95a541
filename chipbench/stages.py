"""Arithmetic over the program's stage spans, for the metric readers.

A traced threads ``run_job`` puts into its ``repro.obs`` ring one
``exec`` span per task, timed by its worker, with the worker thread's
CPU seconds in ``extra["worker_cpu"]``, and inside it the task's stage
spans under the same task id: ``store_decode`` (``extra``: ``bytes``,
``obs``), ``segments.records``, ``segments.pack``, ``segments.device``
(``extra``: ``valid`` and ``allocated`` points), ``segments.reassemble``,
and in a screen cell task ``screen.rows`` and ``screen.kernel``.  A
batched message's stage spans carry its first task id.  Event tuples:
``(ts, dur, name, cat, track, task_id, extra)``.  A program without
these spans or counters reads ``None``.
"""

from __future__ import annotations

#: Task id prefixes: shard tasks of the process phase, screen cell tasks.
SHARD = "store/"
CELL = "screen/"


def under(events, prefix: str) -> list:
    """The spans of tasks whose id starts with ``prefix``."""
    return [e for e in events
            if e[1] >= 0.0 and isinstance(e[5], str)
            and e[5].startswith(prefix)]


def is_segments(name: str) -> bool:
    return name.startswith("segments.")


def share(events, prefix: str, stage) -> float | None:
    """100 x seconds of the spans whose name ``stage(name)`` accepts
    over the ``exec`` seconds, both of the tasks under ``prefix``."""
    spans = under(events, prefix)
    busy = sum(e[1] for e in spans if e[2] == "exec")
    part = sum(e[1] for e in spans if e[2] != "exec" and stage(e[2]))
    if busy <= 0.0 or part <= 0.0:
        return None
    return 100.0 * part / busy


def counters(spans, name: str, key: str) -> list:
    """``extra[key]`` of the spans called ``name`` that carry it."""
    return [e[6][key] for e in spans
            if e[2] == name and isinstance(e[6], dict) and key in e[6]]
