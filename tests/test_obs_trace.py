"""The dispatch audit: per-engine structural records, the ``OBS.json``
regression gates (ceilings, topology skips, disappearing engines), and
the three-way observability coverage lint."""

import copy
import json

from repro.analysis.run import coverage_violations
from repro.obs.run import compare, main, run_obs
from repro.obs.trace import trace_all

# ---------------------------------------------------------------------------
# audit
# ---------------------------------------------------------------------------

def test_trace_subset_records_and_chrome_trace():
    """The records carry the structural counts and no wall-clock field
    (times come from a profiler trace of the served path)."""
    records = trace_all(only="switch_step", reps=2)
    assert records, "substring filter matched no engines"
    for name, rec in records.items():
        assert "skipped" not in rec, name
        assert set(rec) == {"new_executables", "recompiles", "arg_bytes",
                            "out_bytes", "host_transfers"}, name
        assert rec["recompiles"] == 0
        assert rec["host_transfers"] == 0
        assert rec["arg_bytes"] > 0 and rec["out_bytes"] > 0
    json.dumps(records)                     # round-trips


# ---------------------------------------------------------------------------
# OBS.json compare gates (synthetic reports: each gate in isolation)
# ---------------------------------------------------------------------------

def _report(**eng):
    rec = {"new_executables": 1, "recompiles": 0, "host_transfers": 0}
    rec.update(eng)
    return {"schema": 2, "topology": {"n_devices": 1},
            "engines": {"e": rec}, "n_engines": 1, "n_skipped": 0}


def test_compare_clean_baseline_passes():
    base = _report()
    assert compare(copy.deepcopy(base), base) == []


def test_compare_ceilings_zero_headroom():
    base = _report()
    for key in ("new_executables", "recompiles", "host_transfers"):
        new = _report(**{key: base["engines"]["e"][key] + 1})
        regs = compare(new, base)
        assert len(regs) == 1 and key in regs[0] and "ceiling" in regs[0]


def test_compare_topology_change_skips_engine_gates():
    base = _report()
    other = _report(recompiles=5)
    other["topology"] = {"n_devices": 8}
    assert compare(other, base) == []


def test_compare_disappeared_or_skipped_engine_fails():
    base = _report()
    gone = copy.deepcopy(base)
    gone["engines"] = {}
    regs = compare(gone, base)
    assert len(regs) == 1 and "disappeared" in regs[0]
    skipped = copy.deepcopy(base)
    skipped["engines"]["e"] = {"skipped": "no mesh"}
    regs = compare(skipped, base)
    assert len(regs) == 1 and "skipped" in regs[0]
    # a baseline-side skip carries no numbers to gate against
    base_skip = copy.deepcopy(base)
    base_skip["engines"]["e"] = {"skipped": "no mesh"}
    assert compare(copy.deepcopy(base_skip), base_skip) == []


# ---------------------------------------------------------------------------
# driver + coverage lint
# ---------------------------------------------------------------------------

def test_obs_main_writes_reports_and_self_compare_passes(tmp_path):
    out = tmp_path / "OBS.json"
    rc = main(["--only", "switch_step", "--smoke", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["n_engines"] >= 1 and report["engines"]
    assert sorted(tmp_path.iterdir()) == [out]      # no trace file
    # the report gates cleanly against itself
    rc = main(["--only", "switch_step", "--smoke",
               "--json", str(out), "--compare", str(out)])
    assert rc == 0


def test_obs_run_marks_topology_and_host():
    """The report names its topology, which decides whether the gates
    apply, and nothing of the host, since no gate reads it."""
    report = run_obs(only="switch_step", reps=1, with_hlo=False)
    assert report["topology"]["n_devices"] >= 1
    assert "host" not in report


def test_coverage_lint_clean_on_this_repo():
    """Every cache probe is claimed by an engine, every probe_name
    resolves, every engine is traceable — the three observability
    registries agree."""
    assert coverage_violations() == []


def test_coverage_lint_flags_unclaimed_probe():
    from repro.core.switcher import _CACHE_PROBES, register_cache_probe
    register_cache_probe("obs_test_bogus_probe", lambda: 0)
    try:
        v = coverage_violations()
        assert any(x["check"] == "probe_without_engine"
                   and x["path"] == "obs_test_bogus_probe" for x in v)
        assert all(x["path"] == "obs_test_bogus_probe" for x in v)
    finally:
        del _CACHE_PROBES["obs_test_bogus_probe"]
    assert coverage_violations() == []
