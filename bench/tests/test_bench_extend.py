"""A configuration, a traffic mix and a per-layer metric added as new
files and new entries only: the harness runs the new cell and reports the
new metric, with no edit to any file it already had."""
import json

from bench.tests import tinyroot

READER = '''"""prefill.chunks_per_request: prompt chunks per request sent."""


def read(ctx):
    n = len(ctx.window.sent)
    return sum(t.kind == "chunk" for t in ctx.ticks) / n if n else None
'''


def test_new_files_only(tmp_path):
    root = tinyroot.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = tinyroot.tiny_config(d_ff=96, activation="squared_relu")
    (root / "bench" / "configs" / "tiny-sq.json").write_text(json.dumps(cfg))
    mix = dict(tinyroot.TINY_MIX, prompt={"dist": "lognormal", "median": 20,
                                          "sigma": 0.3, "min": 8, "max": 40})
    (root / "bench" / "traffic" / "short.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "prefill.chunks_per_request.py"
     ).write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-sq", "source": "test",
                             "file": "bench/configs/tiny-sq.json",
                             "reduced": [], "why": "squared ReLU"})
    bench["workloads"].append({"name": "tiny-sq.short", "config": "tiny-sq",
                               "traffic": "short", "chips": 1,
                               "why": "short answers"})
    bench["per_layer"].append({
        "name": "prefill.chunks_per_request", "unit": "chunks",
        "better": "lower", "source": "program_span", "layer": "model step",
        "moves": "ttft_p90_s", "workloads": ["tiny-sq.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    import time
    from bench import harness
    from repro.core.hardware_model import HARDWARES
    r = harness.run("tiny-sq.short", 3, 2.0, True, time.monotonic(),
                    root=root, require_tpu=False,
                    hw=HARDWARES["v5e-1chip"], cache=False,
                    trace_dir=str(root / "trace"))
    assert r["correct"], r["checks"]
    assert 0.5 < r["metrics"]["prefill.chunks_per_request"]["value"] <= 2.0
    assert "decode.step_ms" in r["metrics"]
    for p, data in before.items():
        assert p.read_bytes() == data, p
