#!/usr/bin/env python3
"""One benchmark run of one cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration (``configs/<config>.json``), its
traffic mix (``traffic/<mix>.json``, which names its driver
``drivers/<driver>.py`` and its cut), the generator of that kind and cut
(``generators/<kind>_<cut>.py``), the limits of its check
(``limits/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``metrics/<metric>.py``).

Set-up (``setup_s``, from process start to the first timed pass): the
cell's observations from ``--seed`` (:mod:`chipbench.gen`), one CSV per
track, the columnar store built by the program's own ingest
(``repro.store.writer.build_store``), and one warm-up pass that
compiles every program the window runs or loads it from the persistent
cache.  The window then runs whole passes back to
back until one ends after ``--seconds``; an end-to-end rate is the work
of all passes over the window's whole length.  After the window the
last pass's outputs are compared with the float64 reference.

Standard output ends with one JSON line (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number with its limit); standard error
ends with the same numbers.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import importlib.util                                         # noqa: E402
import json                                                   # noqa: E402
import math                                                   # noqa: E402
import os                                                     # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, "work")
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so that only a cell's first run in a checkout compiles.
CACHE_DIR = os.path.join(WORK, "jax_cache")


def use_cache_dir() -> None:
    """Point JAX's compilation cache at :data:`CACHE_DIR`; call before
    JAX is imported.  The program's ``enable_compile_cache`` honours it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a benchmark file (driver or metric reader) by path."""
    name = "chipbench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``BENCHMARK.json`` with every file it names."""

    def __init__(self, name: str, config: dict, traffic: dict, limits: dict,
                 chips: int = 1, end_to_end=(), per_layer=()):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.chips = chips
        self.driver = load_module(os.path.join(
            HERE, "drivers", traffic["driver"] + ".py"))
        self.end_to_end = list(end_to_end)
        self.per_layer = list(per_layer)

    @classmethod
    def load(cls, name: str) -> "Cell":
        bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        entry = cells[name]
        cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        return cls(
            name, _load_json(os.path.join(ROOT, cfg["file"])),
            _load_json(os.path.join(HERE, "traffic",
                                    entry["traffic"] + ".json")),
            _load_json(os.path.join(HERE, "limits", name + ".json")),
            chips=entry["chips"],
            end_to_end=[m for m in bench["end_to_end"]
                        if name in m.get("workloads", [name])],
            per_layer=[m for m in bench["per_layer"]
                       if name in m.get("workloads", [name])])


class CompileLog:
    """Counts XLA compiles (persistent-cache loads included) and their
    seconds, and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self) -> tuple:
        return self.n, self.secs, self.hits


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits 2 without enough TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: this cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def build_data(cell: Cell, seed: int, root: str) -> tuple:
    """Observations from the seed -> one CSV per track -> the store."""
    from chipbench import gen
    from repro.store.writer import build_store
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    tracks = gen.make_tracks(cell.config, cell.traffic, seed)
    csv_dir = os.path.join(root, "csv")
    nbytes = gen.write_csv_tree(tracks, csv_dir)
    store_dir = os.path.join(root, "store")
    manifest = build_store(
        csv_dir, store_dir,
        target_points=cell.config["deployment"]["shard_points"])
    shutil.rmtree(csv_dir)
    gen.check_store(cell.config, cell.traffic, manifest)
    return tracks, store_dir, manifest, nbytes


class Run:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""

    def __init__(self, cell, passes, walls, window_s, workers, events,
                 pipeline_calls, trace, device_kind, tracks, check):
        self.cell = cell
        self.passes = passes
        self.walls = walls                  # host seconds of each pass
        self.window_s = window_s
        self.workers = workers
        self.events = events                # repro.obs tracer events
        self.pipeline_calls = pipeline_calls
        self.trace = trace                  # chipbench.trace.reduce()
        self.device_kind = device_kind
        self.tracks = tracks
        self.check = check
        self._work = {}

    def device_seconds(self, module: str, has: str = None,
                       lacks: str = None) -> float:
        """Device seconds of the program executions whose XLA module name
        matches the pattern ``module`` and that ran an operation of kind
        ``has`` and none of kind ``lacks`` (``trace.op_kind``)."""
        import re
        rx = re.compile(module)
        return sum(d for name, d, kinds in self.trace["programs"]
                   if rx.search(name)
                   and (has is None or has in kinds)
                   and (lacks is None or lacks not in kinds))

    def required(self, what: str) -> tuple:
        """(flops, bytes) that one pass requires, from the reference."""
        from chipbench import work
        if what not in self._work:
            self._work[what] = (work.pipeline(self.tracks)
                                if what == "pipeline"
                                else work.screen(self.check.segs))
        return self._work[what]


def _fmt(x) -> str:
    return repr(x) if isinstance(x, float) else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_cache_dir()
    sys.path.insert(0, ROOT)
    cell = Cell.load(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    info = require_tpu(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program (src/repro) is missing: {e}",
              file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace), info)
    for k, c in result["checks"].items():
        print(f"check: {k} {_fmt(c['value'])} (limit {_fmt(c['limit'])})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def execute(cell: Cell, seed: int, seconds: float, traced: bool,
            info: dict) -> dict:
    """Set-up, window and check of one run; returns the result object.
    ``info`` is the device as :func:`require_tpu` found it."""
    import jax
    from chipbench import trace as tr
    from chipbench import work
    from repro import device
    from repro.kernels import ops

    if traced:
        work.peaks(info["kind"])            # an unknown chip fails early
    print(f"device    : {info['platform']} {info['kind']} x{info['count']}")
    print(f"cache     : {device.enable_compile_cache()}")
    log = CompileLog()
    root = os.path.join(WORK, cell.name)

    t0 = time.perf_counter()
    tracks, store_dir, manifest, nbytes = build_data(cell, seed, root)
    t_data = time.perf_counter() - t0
    n_obs = manifest.n_points
    n_seg = sum(t.n_segments for t in manifest.tracks)
    print(f"data      : {len(tracks)} tracks, {n_obs} observations, "
          f"{n_seg} segments, {len(manifest.shards)} shards "
          f"({nbytes} bytes of CSV; generated and stored in {t_data:.3f}s)")
    for line in cell.traffic.get("reduced", []):
        print(f"reduced: {line}")

    drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
    c0 = log.snap()
    t0 = time.perf_counter()
    drv.run_pass()                                  # warm-up
    t_warm = time.perf_counter() - t0
    c1 = log.snap()
    setup_s = time.perf_counter() - T_START
    print(f"setup     : {setup_s:.3f}s (data {t_data:.3f}s, warm-up pass "
          f"{t_warm:.3f}s); {c1[0] - c0[0]} XLA compiles in the warm-up, "
          f"{c1[1] - c0[1]:.3f}s, {c1[2] - c0[2]} persistent-cache hits")

    tracer = None
    trace_dir = os.path.join(root, "trace")
    if traced:
        from repro.obs import Tracer
        tracer = Tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    calls0 = ops.get_pipeline_stats()
    passes, walls = [], []
    w0 = time.perf_counter()
    while True:
        p0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("pass"):
            passes.append(drv.run_pass(tracer))
        walls.append(time.perf_counter() - p0)
        if len(passes) > 1:
            passes[-2].outputs = None               # keep the last only
        if time.perf_counter() - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    calls1 = ops.get_pipeline_stats()
    c2 = log.snap()
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
              if d.memory_stats() else 0
              for d in jax.devices()[:info["count"]])
    if traced:
        jax.profiler.stop_trace()
    in_window = c2[0] - c1[0]
    print(f"window    : {len(passes)} passes in {window_s:.3f}s; pass "
          f"walls {', '.join(f'{w:.3f}' for w in walls)}")
    print(f"compile   : {in_window} XLA compiles inside the window"
          + ("  <-- the warm-up missed a shape" if in_window else ""))
    print(f"memory    : peak {mem} bytes on the device")

    rate = n_obs * len(passes) / window_s
    values = {"setup_s": setup_s, cell.traffic["rate_metric"]: rate}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end}

    # The check runs once the window is closed and the device memory
    # read: the reference is numpy on the host.
    t0 = time.perf_counter()
    check = cell.driver.Check(tracks, cell.config, cell.limits)
    numbers = check.program(passes[-1])
    print(f"check     : reference and comparison in "
          f"{time.perf_counter() - t0:.3f}s")
    checks = {k: {"value": v, "limit": cell.limits["limits"][k]}
              for k, v in numbers.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    result = {"correct": correct,
              "attempted": sum(p.tasks for p in passes),
              "failed": sum(p.failed for p in passes)}
    if traced:
        red = tr.reduce(tr.read(tr.find_xplane(trace_dir)))
        run = Run(cell, passes, walls, window_s,
                  cell.config["deployment"]["runtime"]["workers"],
                  tracer.events,
                  (calls1["compile_hits"] + calls1["compile_misses"]
                   - calls0["compile_hits"] - calls0["compile_misses"]),
                  red, info["kind"], tracks, check)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        info = dict(info, busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = dict(info, memory_peak_bytes=int(mem))
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
