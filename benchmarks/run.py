"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * paper_tables: Tables I/II + Figs 4-9 + §IV.A/B/C + §V headline
    numbers, reproduced by the calibrated full-scale simulator (scenario
    declarations live in repro.bench.paper);
  * beyond_paper: beyond-paper scenarios (stragglers, speculation, ...);
  * dispatch_bench: protocol-core dispatch throughput (deque vs the old
    O(n^2) list.pop(0) manager);
  * roofline_table: per-(arch x shape x mesh) roofline terms from the
    multi-pod dry-run records (skipped if dryrun hasn't run).

``--backend {threads,processes,sim}`` instead runs one fixed-seed
self-scheduled smoke workload through the unified runtime entry point
(``repro.runtime.run_job``) and writes a structured ``BENCH_smoke.json``
record; it exits non-zero if the record is schema-invalid or any
completion check fails — the CI smoke job is
``benchmarks/run.py --backend sim``.

For the full structured campaign artifact (per-scenario reference deltas,
regression gates), use ``python -m repro.bench.campaign``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

SMOKE_OUT = "BENCH_smoke.json"


def run_backend_smoke(backend: str, out: str = SMOKE_OUT) -> int:
    from repro.bench import (
        Check, RunSpec, Scenario, csv_rows, run_scenario)
    from repro.bench.schema import (
        SCHEMA_VERSION, SMOKE_SCHEMA, validate_smoke)

    sc = Scenario(
        name=f"run_job_{backend}", group="smoke", tier="quick",
        run=RunSpec(dataset="smoke", phase="organize", backend=backend,
                    n_workers=7, nodes=1, nppn=8, tasks_per_message=5),
        checks=(Check("tasks_completed", "within_abs", 200.0, 0.0,
                      "smoke invariant (exactly-once completion)"),
                Check("messages_sent", "within_abs", 40.0, 0.0,
                      "smoke invariant (200 tasks / 5 per message)")))
    record = run_scenario(sc)
    doc = {"schema": SMOKE_SCHEMA, "schema_version": SCHEMA_VERSION,
           "created_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
           "scenario": record}
    problems = validate_smoke(doc)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print("name,us_per_call,derived")
    print(csv_rows([record])[0], flush=True)
    if problems:
        print(f"{out} is SCHEMA-INVALID: " + "; ".join(problems),
              file=sys.stderr)
        return 2
    print(f"wrote {out}")
    if record["status"] != "pass":
        print(f"smoke {record['status']}: {record.get('error') or record['checks']}",
              file=sys.stderr)
        return 1
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    choices=["threads", "processes", "sim"],
                    help="run a fixed-seed run_job smoke workload on one "
                         "execution backend instead of the full suite")
    ap.add_argument("--smoke-out", default=SMOKE_OUT,
                    help=f"smoke artifact path (default {SMOKE_OUT})")
    args = ap.parse_args()
    if args.backend:
        sys.exit(run_backend_smoke(args.backend, args.smoke_out))

    from benchmarks import (beyond_paper, dispatch_bench, paper_tables,
                            roofline_table)

    print("name,us_per_call,derived")
    groups = [("paper", paper_tables.ALL),
              ("beyond", beyond_paper.ALL),
              ("dispatch", dispatch_bench.ALL),
              ("roofline", roofline_table.ALL)]
    failures = 0
    for _gname, fns in groups:
        for fn in fns:
            try:
                for row in fn():
                    print(row, flush=True)
            except Exception as e:     # keep the harness going
                failures += 1
                print(f"{fn.__name__},0,ERROR_{type(e).__name__}",
                      flush=True)
                traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
