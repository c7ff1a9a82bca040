"""Unit tests for scripts/check_bench_regression.py — the CI bench gate
that diffs BENCH_engine.json against a fresh run. The script lives
outside the package (scripts/), so it is loaded by file path; every test
drives the pure comparison functions on synthetic result dicts."""

import importlib.util
import json
import math
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "scripts" / \
    "check_bench_regression.py"


@pytest.fixture(scope="module")
def gate():
    spec = importlib.util.spec_from_file_location("check_bench_regression",
                                                  _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _doc(**sections):
    base = {"schema": 1,
            "config": {"trace_seeds": {"mixed": 0, "long": 3}}}
    base.update(sections)
    return base


# ---------------------------------------------------------------- compare --
def test_compare_flags_large_drop(gate):
    baseline = _doc(mixed={"n": 16, "engine_tok_s": 100.0})
    fresh = _doc(mixed={"n": 16, "engine_tok_s": 80.0})   # -20%
    rows, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert len(failures) == 1 and "mixed.engine_tok_s" in failures[0]
    (row,) = rows
    assert row[0] == "mixed.engine_tok_s" and row[4].startswith("FAIL")


def test_compare_within_tolerance_passes(gate):
    baseline = _doc(mixed={"n": 16, "engine_tok_s": 100.0})
    fresh = _doc(mixed={"n": 16, "engine_tok_s": 90.0})    # -10%
    rows, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert failures == []
    assert rows[0][4] == "OK"
    # improvements never fail, whatever the magnitude
    fresh["mixed"]["engine_tok_s"] = 500.0
    _, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert failures == []


def test_compare_skips_mismatched_trace_sizes(gate):
    """A 4-request CI smoke is not comparable to a 16-request baseline:
    the drop must be reported as SKIP, not FAIL."""
    baseline = _doc(mixed={"n": 16, "engine_tok_s": 100.0})
    fresh = _doc(mixed={"n": 4, "engine_tok_s": 20.0})
    rows, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert failures == []
    assert "SKIP" in rows[0][4] and "size" in rows[0][4]
    assert not gate.sizes_match(baseline, fresh, "mixed")
    assert gate.sizes_match(baseline, baseline, "mixed")
    # a section without n is never comparable
    assert not gate.sizes_match(_doc(kv={"decode_tok_s": 1.0}),
                                _doc(kv={"decode_tok_s": 1.0}), "kv")


def test_compare_missing_and_new_sections(gate):
    baseline = _doc(mixed={"n": 16, "engine_tok_s": 100.0})
    fresh = _doc(kv={"n": 12, "fp16": {"decode_tok_s": 50.0}})
    rows, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert failures == []                        # missing != regressed
    by_path = {r[0]: r for r in rows}
    assert "SKIP" in by_path["mixed.engine_tok_s"][4]
    assert "NEW" in by_path["kv.fp16.decode_tok_s"][4]


def test_compare_only_reads_tok_s_leaves(gate):
    """Non-throughput leaves (preemptions, ms percentiles) never gate."""
    baseline = _doc(longprompt={"n": 6, "chunked": {
        "decode_tok_s": 100.0, "stall_p99_ms": 1.0, "prefill_chunks": 93}})
    fresh = _doc(longprompt={"n": 6, "chunked": {
        "decode_tok_s": 100.0, "stall_p99_ms": 99.0, "prefill_chunks": 5}})
    rows, failures = gate.compare(baseline, fresh, tolerance=0.15)
    assert failures == []
    assert [r[0] for r in rows] == ["longprompt.chunked.decode_tok_s"]


# ----------------------------------------------------- fresh-only checks --
def test_check_sharded_floors(gate):
    ok = _doc(sharded={"outputs_identical": True,
                       "capacity": {"pages_scaling_2x": 2.0}})
    rows, failures = gate.check_sharded(ok)
    assert failures == [] and all(r[4] == "OK" for r in rows)

    diverged = _doc(sharded={"outputs_identical": False,
                             "capacity": {"pages_scaling_2x": 1.2}})
    _, failures = gate.check_sharded(diverged)
    assert len(failures) == 2
    assert any("diverged" in f for f in failures)

    assert gate.check_sharded(_doc()) == ([], [])


def _autotune_section(**over):
    sec = {"n": 4, "budget": 32, "candidates": 12, "admissible": 10,
           "default": {"decode_tok_s": 300.0},
           "searched": {"decode_tok_s": 360.0},
           "searched_vs_default": 1.2}
    sec.update(over)
    return sec


def test_check_autotune_floors(gate):
    ok = _doc(autotune=_autotune_section())
    rows, failures = gate.check_autotune(ok)
    assert failures == [] and all(r[4] == "OK" for r in rows)
    # exactly at the floor passes
    _, failures = gate.check_autotune(
        _doc(autotune=_autotune_section(searched_vs_default=0.95)))
    assert failures == []
    # a searched config that measured worse than the default fails
    _, failures = gate.check_autotune(
        _doc(autotune=_autotune_section(searched_vs_default=0.9)))
    assert len(failures) == 1 and "searched_vs_default" in failures[0]
    # a search that evaluated nothing fails
    _, failures = gate.check_autotune(
        _doc(autotune=_autotune_section(candidates=0, admissible=0)))
    assert len(failures) == 2
    # missing section -> no rows; missing keys -> SKIP, not crash
    assert gate.check_autotune(_doc()) == ([], [])
    rows, failures = gate.check_autotune(_doc(autotune={"n": 4}))
    assert failures == [] and all("SKIP" in r[4] for r in rows)


def test_validate_schema_autotune_required_keys(gate):
    assert gate.validate_schema(_doc(autotune=_autotune_section())) == []
    sec = _autotune_section()
    del sec["searched_vs_default"]
    del sec["budget"]
    problems = gate.validate_schema(_doc(autotune=sec), "fresh")
    assert any("searched_vs_default" in p for p in problems)
    assert any("budget" in p for p in problems)
    # default/searched sub-objects must carry the measured tok/s the
    # floors and the trajectory read
    problems = gate.validate_schema(
        _doc(autotune=_autotune_section(searched={"ttft_p50_ms": 1.0})))
    assert any("autotune.searched" in p and "decode_tok_s" in p
               for p in problems)
    problems = gate.validate_schema(_doc(autotune="not a dict"))
    assert any("not an object" in p for p in problems)


# -------------------------------------------------------- schema validate --
def test_validate_schema_accepts_committed_baseline(gate):
    repo = pathlib.Path(__file__).resolve().parents[1]
    doc = json.loads((repo / "BENCH_engine.json").read_text())
    assert gate.validate_schema(doc) == []


def test_validate_schema_rejects_nan_and_inf(gate):
    doc = _doc(mixed={"n": 16, "engine_tok_s": math.nan})
    problems = gate.validate_schema(doc, "fresh")
    assert len(problems) == 1 and "NaN" in problems[0]
    assert "mixed.engine_tok_s" in problems[0]

    doc = _doc(telemetry={"roofline_scale": {"decode": math.inf}})
    problems = gate.validate_schema(doc)
    assert any("non-finite" in p for p in problems)
    # None (null) is fine — unpredicted calibration groups use it
    assert gate.validate_schema(
        _doc(telemetry={"roofline_scale": {"decode": None}})) == []


def test_validate_schema_requires_seeds_and_version(gate):
    assert any("trace_seeds" in p for p in gate.validate_schema(
        {"schema": 1, "config": {}}))
    assert any("trace_seeds" in p for p in gate.validate_schema(
        {"schema": 1, "config": {"trace_seeds": {}}}))
    assert any("schema" in p for p in gate.validate_schema(
        {"config": {"trace_seeds": {"mixed": 0}}}))
    assert gate.validate_schema("not a dict") == ["doc: not a JSON object"]
    # NaN inside a list leaf is still caught
    problems = gate.validate_schema(
        _doc(extra={"xs": [1.0, math.nan]}))
    assert any("extra.xs.1" in p for p in problems)


# ------------------------------------------------------------ end-to-end --
def test_gate_cli_fails_on_schema_violation(gate, tmp_path):
    """The CLI exits 1 on a NaN fresh doc BEFORE comparing (a NaN tok/s
    would otherwise sail through every delta check)."""
    baseline = _doc(mixed={"n": 16, "engine_tok_s": 100.0})
    fresh = _doc(mixed={"n": 16, "engine_tok_s": math.nan})
    bp, fp = tmp_path / "base.json", tmp_path / "fresh.json"
    bp.write_text(json.dumps(baseline))
    fp.write_text(json.dumps(fresh))
    proc = subprocess.run(
        [sys.executable, str(_SCRIPT), "--baseline", str(bp),
         "--fresh", str(fp)], capture_output=True, text=True)
    assert proc.returncode == 1
    assert "SCHEMA" in proc.stdout and "NaN" in proc.stdout

    fp.write_text(json.dumps(_doc(mixed={"n": 16, "engine_tok_s": 99.0})))
    proc = subprocess.run(
        [sys.executable, str(_SCRIPT), "--baseline", str(bp),
         "--fresh", str(fp)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout
    assert "no regressions" in proc.stdout
