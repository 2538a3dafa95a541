"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

The trace of a ``--trace 1`` run holds the device planes
(``/device:TPU:<n>``, with one line of XLA program executions and one
of XLA operations) and the host plane, where the benchmark's own
``jax.profiler.TraceAnnotation`` spans sit (``pass``, ``process.job``,
``screen.plan``, ``screen.cells``).  :func:`reduce` turns them into:

* ``window_s`` -- first ``pass`` start to last ``pass`` end;
* ``busy_s`` -- the union of the device's operation intervals inside
  the window, averaged over the device planes;
* ``programs`` -- every program execution in the window, with the
  kinds of operation it ran (programs jitted from a ``functools.partial``
  all carry the module name ``jit__unknown``; what they run tells them
  apart);
* ``device_ops`` -- the operation kinds that took most device time
  (a ``while`` counts its body as well);
* ``idle_gaps`` -- the longest gaps in the busy union, each named by
  the innermost benchmark annotation open at its middle.

It reads the file with nothing but ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

ANNOTATIONS = ("pass", "process.job", "screen.plan", "screen.cells")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"\.\d+$")


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log dir."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Merged, sorted intervals of (starts, ends)."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > run_end[:-1]]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def module_name(name: str) -> str:
    """An XLA module's name without its ``(<id>)`` suffix."""
    return _SUFFIX.sub("", name)


def op_kind(name: str) -> str:
    """An XLA operation's kind: its HLO name without the numeric
    suffix (``%track_interp_pallas.1 = ...`` -> ``%track_interp_pallas``)."""
    head = name.split(" ", 1)[0]
    return _OP_SUFFIX.sub("", head)


def read(path: str) -> dict:
    """Raw intervals of a trace, in seconds on one clock:
    ``{"devices": [{"ops": (starts, ends, kinds), "modules": [(name, s,
    e)]}], "annotations": [(name, s, e)]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops_s, ops_e, kinds, modules = [], [], [], []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    for ev in line.events:
                        ops_s.append(ev.start_ns)
                        ops_e.append(ev.start_ns + ev.duration_ns)
                        kinds.append(op_kind(ev.name))
                elif line.name == _MODULES_LINE:
                    for ev in line.events:
                        modules.append((module_name(ev.name),
                                        ev.start_ns * 1e-9,
                                        (ev.start_ns + ev.duration_ns)
                                        * 1e-9))
            devices.append({"ops": (np.asarray(ops_s, np.float64) * 1e-9,
                                    np.asarray(ops_e, np.float64) * 1e-9,
                                    kinds),
                            "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ANNOTATIONS:
                        annotations.append(
                            (ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "annotations": annotations}


def reduce(raw: dict, top: int = 10) -> dict:
    """Device numbers of a traced window (see the module docstring).

    ``programs`` lists every program execution inside the window as
    ``(module name, seconds, kinds of the operations it ran)``, so a
    reader can pick programs out by what they run where their module
    names do not tell them apart."""
    passes = [a for a in raw["annotations"] if a[0] == "pass"]
    if not passes:
        raise ValueError("the trace holds no 'pass' annotation")
    if not raw["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    w0 = min(a[1] for a in passes)
    w1 = max(a[2] for a in passes)
    busy, gaps, programs = [], [], []
    by_kind: dict = {}
    for dev in raw["devices"]:
        s, e, kinds = dev["ops"]
        cs, ce = np.clip(s, w0, w1), np.clip(e, w0, w1)
        us, ue = _union(cs[ce > cs], ce[ce > cs])
        busy.append(float((ue - us).sum()))
        gs = np.r_[w0, ue]
        ge = np.r_[us, w1]
        keep = ge > gs
        gaps.extend(zip(gs[keep].tolist(), ge[keep].tolist()))
        for k, d in zip(kinds, (ce - cs).tolist()):
            by_kind[k] = by_kind.get(k, 0.0) + d
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = np.asarray([m[1] for m in mods])
        owner = np.searchsorted(starts, s, side="right") - 1
        held: list = [set() for _ in mods]
        for i, k, a in zip(owner.tolist(), kinds, s.tolist()):
            if i >= 0 and a <= mods[i][2]:
                held[i].add(k)
        for (name, a, b), ks in zip(mods, held):
            d = min(b, w1) - max(a, w0)
            if d > 0:
                programs.append((name, d, frozenset(ks)))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        mid = 0.5 * (a + b)
        inner = [x for x in raw["annotations"] if x[1] <= mid <= x[2]]
        name = (max(inner, key=lambda x: (x[1], -x[2]))[0] if inner
                else "none")
        named.append([name, b - a])
    ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": w1 - w0,
            "busy_s": float(np.mean(busy)),
            "programs": programs,
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": named}
