"""Scenario-matrix benchmark campaigns with structured BENCH artifacts.

The paper *is* a benchmark; this package makes its reproduction — and
every beyond-paper perf claim this repo adds — declarative, diffable, and
regression-gated:

  * :mod:`repro.bench.scenarios` — the input language: RunSpec/Scenario/
    Check/FaultProfile plus the :func:`expand` matrix helper;
  * :mod:`repro.bench.paper` / :mod:`repro.bench.beyond` — the declared
    matrix (Tables I/II cells, §IV/§V claims, live smokes, future-work
    sweeps);
  * :mod:`repro.bench.engine` — expands scenarios into
    :func:`repro.runtime.run_job` invocations and emits BENCH records;
  * :mod:`repro.bench.schema` — artifact validation + deterministic
    canonical serialization;
  * :mod:`repro.bench.campaign` — the ``python -m repro.bench.campaign``
    CLI (``--quick`` is the CI tier);
  * :mod:`repro.bench.storage` — storage-layer matrix: the columnar
    track store vs the CSV-zip path (``BENCH_storage.json``; also
    ``python -m repro.bench.storage`` / ``campaign --storage``);
  * :mod:`repro.bench.compare` — regression-diff two artifacts
    (dispatches on the ``schema`` field).
"""

from repro.bench.beyond import beyond_scenarios
from repro.bench.engine import (
    csv_rows, execute_spec, run_campaign, run_scenario, summary_lines)
from repro.bench.paper import (
    PAPER_TABLE1, PAPER_TABLE2, TABLE_TOLERANCE, paper_scenarios,
    smoke_scenarios)
from repro.bench.scenarios import (
    Check, FAULT_PROFILES, FaultProfile, RunSpec, Scenario, expand)
from repro.bench.schema import (
    CAMPAIGN_SCHEMA, SMOKE_SCHEMA, STORAGE_SCHEMA, canonical_bytes,
    validate_campaign, validate_record, validate_storage)
from repro.bench.storage import (
    StorageScenario, StorageSpec, run_storage_campaign,
    run_storage_scenario, storage_scenarios)

__all__ = [
    "Check", "FAULT_PROFILES", "FaultProfile", "RunSpec", "Scenario",
    "expand",
    "PAPER_TABLE1", "PAPER_TABLE2", "TABLE_TOLERANCE",
    "paper_scenarios", "smoke_scenarios", "beyond_scenarios",
    "csv_rows", "execute_spec", "run_campaign", "run_scenario",
    "summary_lines",
    "StorageScenario", "StorageSpec", "storage_scenarios",
    "run_storage_campaign", "run_storage_scenario",
    "CAMPAIGN_SCHEMA", "SMOKE_SCHEMA", "STORAGE_SCHEMA",
    "canonical_bytes", "validate_campaign", "validate_record",
    "validate_storage",
]


def all_scenarios():
    """The full declared matrix (paper + smokes + beyond), campaign order."""
    from repro.bench.campaign import all_scenarios as _all
    return _all()
