"""Storage benchmark matrix: schema validity, deterministic metrics,
and the schema-dispatching compare CLI."""

import copy

import pytest

from repro.bench import storage
from repro.bench.compare import compare_docs, default_metric
from repro.bench.schema import (
    STORAGE_SCHEMA, canonical_bytes, validate_storage)


@pytest.fixture(scope="module")
def storage_doc():
    """One quick-tier run on a small fixture (shared across tests)."""
    return storage.run_storage_campaign(quick=True)


def test_scenario_matrix_declares_acceptance_cell():
    names = {sc.name for sc in storage.storage_scenarios()}
    assert "storage_feed_heavy_tail_store_prefetch" in names
    quick = [sc for sc in storage.storage_scenarios()
             if sc.tier == "quick"]
    assert quick, "quick tier must not be empty (CI gates on it)"
    for sc in quick:
        metrics = {c.metric for c in sc.checks}
        assert {"feed_speedup_x", "feed_bitwise_equal",
                "rebuild_identical"} <= metrics


def test_storage_doc_schema_valid(storage_doc):
    assert validate_storage(storage_doc) == []
    assert storage_doc["schema"] == STORAGE_SCHEMA
    assert storage_doc["summary"]["error"] == 0


def test_storage_deterministic_metrics(storage_doc):
    """The deterministic half of the acceptance cell must hold in
    tier-1 (wall-clock speedup is gated by CI's store-smoke job, not
    here, so a loaded test machine can't flake the suite)."""
    rec = storage_doc["scenarios"][0]
    m = rec["metrics"]
    assert m["feed_bitwise_equal"] == 1.0
    assert m["rebuild_identical"] == 1.0
    assert m["n_points"] > 0 and m["n_tracks"] > 0
    assert m["bytes_on_disk"] > 0
    # zlib columns beat the CSV-in-zip encoding on bytes per point
    assert m["bytes_per_point"] < m["baseline_bytes_on_disk"] / m["n_points"]
    assert "feed_speedup_x" in rec["measured"]


def test_storage_canonical_bytes_reproducible(storage_doc):
    """Two same-seed campaign runs agree byte-for-byte after stripping
    the nondeterministic keys.  The quick tier gates wall-clock
    throughput, so ``checks`` (which record the
    measured actuals) are stripped too — ``metrics`` is the
    reproducible surface."""
    import json

    def strip_checks(blob):
        doc = json.loads(blob)
        for rec in doc["scenarios"]:
            rec.pop("checks", None)
            rec.pop("status", None)      # depends on the measured check
        return json.dumps(doc, sort_keys=True)

    again = storage.run_storage_campaign(quick=True)
    assert strip_checks(canonical_bytes(storage_doc)) == \
        strip_checks(canonical_bytes(again))


def test_prefetch_wait_attribution_fake_clock(storage_doc):
    """The quantity behind ``prefetch_wait_frac`` must be exact under an
    injected monotonic clock — attribution is asserted on fake-clock
    units, never on wall-time ratios (which flake on loaded machines)."""
    from repro.store.reader import TrackStore

    fx = storage._fixture(storage.StorageSpec())

    class Tick:
        t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    store = TrackStore(fx["store_root"], clock=Tick())
    n = len(list(store.iter_batches(prefetch=0)))
    assert n == fx["n_shards"] > 0
    assert store.stats["decode_s"] == n          # one tick per decode
    assert store.stats["wait_s"] == 0.0
    frozen = TrackStore(fx["store_root"], clock=lambda: 0.0)
    assert len(list(frozen.iter_batches(prefetch=2))) == n
    assert frozen.stats["wait_s"] == 0.0         # no wall-time leaks


def test_spec_validation():
    with pytest.raises(ValueError):
        storage.StorageSpec(source="tape")
    with pytest.raises(ValueError):
        storage.StorageSpec(phase="tepid")
    with pytest.raises(ValueError):
        storage.StorageSpec(consume="eat")
    with pytest.raises(ValueError):
        storage.StorageSpec(workload="nope")


# ---------------------------------------------------------------------------
# compare.py schema dispatch (satellite).
# ---------------------------------------------------------------------------

def _fake_doc(schema, metric, values):
    return {"schema": schema,
            "scenarios": [{"name": n, "metrics": {metric: v}}
                          for n, v in values.items()]}


def test_compare_dispatches_on_schema(storage_doc):
    assert default_metric(storage_doc) == "bytes_per_point"
    assert default_metric({"schema": "repro.bench.campaign/v1"}) == \
        "job_seconds"
    with pytest.raises(ValueError):
        default_metric({"schema": "repro.bench.unknown/v9"})


def test_compare_storage_regression_gate(storage_doc):
    worse = copy.deepcopy(storage_doc)
    for rec in worse["scenarios"]:
        rec["metrics"]["bytes_per_point"] *= 1.5
    rows, regs = compare_docs(storage_doc, worse, threshold=0.10)
    assert regs and all(r["regressed"] for r in regs)
    rows2, regs2 = compare_docs(storage_doc, storage_doc,
                                threshold=0.10)
    assert regs2 == []


def test_compare_rejects_schema_mismatch(storage_doc):
    campaign = _fake_doc("repro.bench.campaign/v1", "job_seconds",
                         {"a": 0.5})
    with pytest.raises(ValueError, match="different schemas"):
        compare_docs(storage_doc, campaign)


def test_compare_smoke_single_record():
    old = {"schema": "repro.bench.smoke/v1",
           "scenario": {"name": "s", "metrics": {"job_seconds": 10.0}}}
    new = {"schema": "repro.bench.smoke/v1",
           "scenario": {"name": "s", "metrics": {"job_seconds": 20.0}}}
    rows, regs = compare_docs(old, new, threshold=0.10)
    assert len(rows) == 1 and len(regs) == 1
