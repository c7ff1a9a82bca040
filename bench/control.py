"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 30

For each seed, one full run of the cell (set-up, the measured window, the
float32 reference over the seeded sample), and on the same sample the
control: the reference computed with every matmul operand in float8 e4m3
(bench/reference.py), the nearest precision below the bfloat16 the
configuration states. The control's gaps take the served tokens' place
in the harness's own decision (``harness.passed``), which has to find
the control not correct. Prints, per seed, the served tokens' widest gap
(the lower reading comes from sound runs), the control's widest gap (the
upper reading) and the control's verdict, and appends them as JSON lines
to ``chiprun_out/control-<cell>.jsonl``. The benchmark's own runs never
compute the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    out = os.path.join(ROOT, "chiprun_out", f"control-{a.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for seed in a.seeds:
        r = harness.run(a.workload, seed, a.seconds, False, time.monotonic(),
                        control=True)
        c = r["checks"]
        ctl = dict(c, max_logit_gap=c["control_max_logit_gap"])
        row = {"workload": a.workload, "seed": seed,
               "correct": r["correct"],
               "control_correct": harness.passed(ctl),
               "served_gap": c["max_logit_gap"]["value"],
               "control_gap": c["control_max_logit_gap"]["value"],
               "served_tokens": c["served_tokens_compared"]["value"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
