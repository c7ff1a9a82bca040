"""CI bench-regression gate for BENCH_engine.json.

Diffs a freshly produced bench result against the committed baseline and
fails (exit 1) when any decode-throughput metric drops more than the
tolerance (default 15%). The comparison table is always printed, one row
per ``*tok_s`` leaf, so a red gate shows exactly which trace regressed and
a green gate still documents the trajectory.

Sections are only compared when both files ran the same trace size (their
``n`` keys match) — a CI smoke at 4 requests is not comparable to a
12-request baseline and is reported as SKIP rather than silently passed.

The sharded section (multi-device CI job) carries its own acceptance
invariants, checked from the fresh file alone (same-machine ratios and
booleans, so they transfer across runner classes):

* ``outputs_identical == true`` — greedy outputs on the mesh must be
  token-identical to the 1-device engine;
* ``capacity.pages_scaling_2x >= 1.9`` — per-device pool capacity must
  scale >= 1.9x from 1 to 2 model shards (the kv-head split really halves
  per-device page bytes).

The autotune section (serving-stack autotuner) likewise carries
fresh-only invariants:

* ``searched_vs_default >= 0.95`` — the searched config's *measured*
  decode tok/s may never fall below 0.95x the hand-picked default (the
  default is in the validation set, so the tuner can only tie or win);
* ``candidates >= 1`` and ``admissible >= 1`` — the search actually
  evaluated something.

Before any comparison both files are **schema-validated**: a bench doc
must carry a ``schema`` version, a non-empty ``config.trace_seeds`` list
(the seeds the traces were drawn from — a doc without them is not
reproducible), and no NaN/Inf anywhere in its numeric leaves (a NaN
tok/s would sail through every ``delta < -tolerance`` comparison as a
silent pass). Validation failures exit 1 before the gate runs.

Absolute tok/s values are machine-dependent: regenerate the committed
baseline (``python -m benchmarks.bench_engine_throughput``) when the CI
runner class changes, or tune ``--tolerance`` via the BENCH_GATE_TOL env
var.

Usage::

    python scripts/check_bench_regression.py \
        --baseline BENCH_engine.json --fresh BENCH_fresh.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SHARDED_PAGES_SCALING_MIN = 1.9
AUTOTUNE_RATIO_MIN = 0.95
AUTOTUNE_CANDIDATES_MIN = 1

# required keys of the bench's ``autotune`` section (when present) —
# the gate's floors read these, so a doc that drops one is malformed,
# not merely incomplete
AUTOTUNE_REQUIRED_KEYS = (
    "n",
    "budget",
    "candidates",
    "admissible",
    "default",
    "searched",
    "searched_vs_default",
)


def numeric_leaves(node, path=()):
    """Yield (dotted_path, value) for EVERY numeric leaf (bools excluded)."""
    if isinstance(node, dict):
        for key in sorted(node):
            yield from numeric_leaves(node[key], path + (str(key),))
    elif isinstance(node, list):
        for i, item in enumerate(node):
            yield from numeric_leaves(item, path + (str(i),))
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        yield ".".join(path), float(node)


def validate_schema(doc, name="doc"):
    """Structural sanity of one bench document; returns a list of problem
    strings (empty = valid). Checked before any comparison: a NaN leaf
    would pass every ``delta < -tolerance`` check silently, and a doc
    without its trace seeds is not reproducible."""
    problems = []
    if not isinstance(doc, dict):
        return [f"{name}: not a JSON object"]
    if "schema" not in doc:
        problems.append(f"{name}: missing 'schema' version key")
    seeds = (doc.get("config") or {}).get("trace_seeds") \
        if isinstance(doc.get("config"), dict) else None
    if not seeds or not isinstance(seeds, (dict, list)):
        problems.append(
            f"{name}: missing or empty config.trace_seeds "
            "(bench traces must record their seeds)")
    autotune = doc.get("autotune")
    if autotune is not None:
        if not isinstance(autotune, dict):
            problems.append(f"{name}: autotune section is not an object")
        else:
            for key in AUTOTUNE_REQUIRED_KEYS:
                if key not in autotune:
                    problems.append(f"{name}: autotune missing '{key}'")
            for side in ("default", "searched"):
                sub = autotune.get(side)
                if isinstance(sub, dict) and "decode_tok_s" not in sub:
                    problems.append(
                        f"{name}: autotune.{side} missing 'decode_tok_s'")
    for path, value in numeric_leaves(doc):
        if value != value:                       # NaN
            problems.append(f"{name}: NaN at {path}")
        elif value in (float("inf"), float("-inf")):
            problems.append(f"{name}: non-finite value at {path}")
    return problems


def tok_s_leaves(node, path=()):
    """Yield (dotted_path, value) for every numeric ``*tok_s`` leaf."""
    if isinstance(node, dict):
        for key in sorted(node):
            yield from tok_s_leaves(node[key], path + (str(key),))
    elif isinstance(node, (int, float)) and path:
        if path[-1].endswith("tok_s"):
            yield ".".join(path), float(node)


def section_of(path):
    return path.split(".", 1)[0]


def sizes_match(baseline, fresh, section):
    b, f = baseline.get(section), fresh.get(section)
    if not isinstance(b, dict) or not isinstance(f, dict):
        return False
    # a section without a recorded trace size is never comparable
    return b.get("n") is not None and b.get("n") == f.get("n")


def compare(baseline, fresh, tolerance):
    """Build comparison rows; returns (rows, failures)."""
    rows = []
    failures = []
    base_vals = dict(tok_s_leaves(baseline))
    fresh_vals = dict(tok_s_leaves(fresh))
    for path, base in sorted(base_vals.items()):
        section = section_of(path)
        got = fresh_vals.get(path)
        if got is None:
            rows.append((path, base, None, None, "SKIP (missing in fresh)"))
            continue
        if not sizes_match(baseline, fresh, section):
            rows.append((path, base, got, None, "SKIP (trace size differs)"))
            continue
        delta = (got - base) / base if base else 0.0
        if delta < -tolerance:
            status = f"FAIL (> {tolerance:.0%} drop)"
            failures.append(f"{path}: {base:.1f} -> {got:.1f} ({delta:+.1%})")
        else:
            status = "OK"
        rows.append((path, base, got, delta, status))
    for path in sorted(set(fresh_vals) - set(base_vals)):
        rows.append((path, None, fresh_vals[path], None, "NEW (no baseline)"))
    return rows, failures


def check_sharded(fresh):
    """Acceptance invariants of the sharded-engine section (fresh-only:
    both are same-machine ratios/booleans, so they transfer across runner
    classes)."""
    rows = []
    failures = []
    section = fresh.get("sharded")
    if not isinstance(section, dict):
        return rows, failures
    path = "sharded.outputs_identical"
    ident = section.get("outputs_identical")
    if ident is None:
        rows.append((path, True, None, None, "SKIP (not recorded)"))
    elif ident:
        rows.append((path, True, True, None, "OK"))
    else:
        rows.append((path, True, False, None, "FAIL (diverged)"))
        failures.append(
            f"{path}: sharded engine diverged from the 1-device engine"
        )
    path = "sharded.capacity.pages_scaling_2x"
    floor = SHARDED_PAGES_SCALING_MIN
    scaling = (section.get("capacity") or {}).get("pages_scaling_2x")
    if scaling is None:
        rows.append((path, floor, None, None, "SKIP (not recorded)"))
    elif scaling >= floor:
        rows.append((path, floor, scaling, None, "OK"))
    else:
        rows.append((path, floor, scaling, None, f"FAIL (< {floor})"))
        failures.append(f"{path}: {scaling:.2f} below the {floor} floor")
    return rows, failures


def check_autotune(fresh):
    """Acceptance invariants of the autotune section (fresh-only: the
    searched/default ratio is two same-machine measurements, so it
    transfers across runner classes)."""
    rows = []
    failures = []
    section = fresh.get("autotune")
    if not isinstance(section, dict):
        return rows, failures
    path = "autotune.searched_vs_default"
    floor = AUTOTUNE_RATIO_MIN
    ratio = section.get("searched_vs_default")
    if ratio is None:
        rows.append((path, floor, None, None, "SKIP (not recorded)"))
    elif ratio >= floor:
        rows.append((path, floor, ratio, None, "OK"))
    else:
        rows.append((path, floor, ratio, None, f"FAIL (< {floor})"))
        failures.append(
            f"{path}: searched config measured {ratio:.2f}x the default "
            f"(floor {floor}x) — the autotuner shipped a regression"
        )
    for key in ("candidates", "admissible"):
        path = f"autotune.{key}"
        floor = AUTOTUNE_CANDIDATES_MIN
        count = section.get(key)
        if count is None:
            rows.append((path, floor, None, None, "SKIP (not recorded)"))
        elif count >= floor:
            rows.append((path, floor, count, None, "OK"))
        else:
            rows.append((path, floor, count, None, f"FAIL (< {floor})"))
            failures.append(
                f"{path}: {count} below the {floor} floor "
                "(the search evaluated nothing)"
            )
    return rows, failures


def _fmt(value):
    if value is None:
        return "-"
    if isinstance(value, (str, bool)):
        return str(value)
    return f"{value:.2f}"


def print_table(rows, headers):
    widths = [len(h) for h in headers]
    rendered = []
    for row in rows:
        cells = [_fmt(value) for value in row]
        rendered.append(cells)
        widths = [max(w, len(c)) for w, c in zip(widths, cells)]
    line = "  ".join(h.ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-" * len(line))
    for cells in rendered:
        print("  ".join(c.ljust(w) for c, w in zip(cells, widths)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--baseline", default="BENCH_engine.json")
    parser.add_argument("--fresh", required=True)
    parser.add_argument(
        "--tolerance",
        type=float,
        default=float(os.environ.get("BENCH_GATE_TOL", "0.15")),
        help="max allowed fractional decode tok/s drop (default 0.15)",
    )
    args = parser.parse_args()

    with open(args.baseline) as fh:
        baseline = json.load(fh)
    with open(args.fresh) as fh:
        fresh = json.load(fh)

    problems = validate_schema(baseline, "baseline") \
        + validate_schema(fresh, "fresh")
    if problems:
        for problem in problems:
            print(f"SCHEMA: {problem}")
        return 1

    rows, failures = compare(baseline, fresh, args.tolerance)
    table = [
        (
            path,
            base,
            got,
            None if delta is None else f"{delta:+.1%}",
            status,
        )
        for path, base, got, delta, status in rows
    ]
    print(f"bench gate: tolerance {args.tolerance:.0%} decode tok/s drop")
    print_table(table, ("metric", "baseline", "fresh", "delta", "status"))

    sh_rows, sh_failures = check_sharded(fresh)
    failures.extend(sh_failures)
    if sh_rows:
        print()
        print("sharded-engine acceptance (fresh run, machine-independent):")
        print_table(
            [(p, f, v, s) for p, f, v, _, s in sh_rows],
            ("invariant", "floor", "value", "status"),
        )

    at_rows, at_failures = check_autotune(fresh)
    failures.extend(at_failures)
    if at_rows:
        print()
        print("autotune acceptance (fresh run, machine-independent):")
        print_table(
            [(p, f, v, s) for p, f, v, _, s in at_rows],
            ("invariant", "floor", "value", "status"),
        )

    print()
    if failures:
        for failure in failures:
            print(f"REGRESSION: {failure}")
        return 1
    print("bench gate: no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
