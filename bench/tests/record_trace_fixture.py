"""Record the trace fixture of test_bench_xplane.py on a TPU.

    python3 bench/tests/record_trace_fixture.py

A tiny dense cell (head_dim 128, so the Pallas paged-attention kernel
tiles) is served through the engine on one chip; three ``Engine.step``s,
each inside a ``bench.step`` annotation, are traced with jax.profiler, and
the ``.xplane.pb`` is written to chiprun_out/engine_tiny.xplane.pb together
with what bench/xplane.py reads from it (engine_tiny.json)."""
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness, xplane  # noqa: E402
from bench.tests import tinyroot  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

SHAPE = dict(d_model=256, num_heads=2, num_kv_heads=1, head_dim=128,
             d_ff=512, vocab_size=1024)


def main():
    tmp = Path(tempfile.mkdtemp())
    root = tinyroot.make_root(tmp, **SHAPE)
    cell = harness.load_cell("tiny.chat", root)
    devices = harness.check_devices(1)
    engine, _ = harness.build(cell, 7, devices, harness.log)
    harness.warm_up(engine, SHAPE["vocab_size"])
    rng = np.random.default_rng(0)
    engine.submit(Request(rid=0, prompt=rng.integers(
        2, SHAPE["vocab_size"], 30).astype(np.int32), max_new=8))
    engine.step()
    tdir = str(tmp / "trace")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.client"):
        for rid, n in enumerate((40, 200), start=1):
            engine.submit(Request(rid=rid, prompt=rng.integers(
                2, SHAPE["vocab_size"], n).astype(np.int32), max_new=4))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
    jax.profiler.stop_trace()
    src = xplane.newest(tdir)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    shutil.copy(src, out / "engine_tiny.xplane.pb")
    tr = xplane.reduce(src)
    summary = {
        "bytes": os.path.getsize(src), "window_s": tr.window_s,
        "busy_s": tr.busy_s, "devices": tr.devices,
        "device_ops": tr.device_ops(), "idle_gaps": tr.idle_gaps(),
        "decode_spans": len(tr.spans("_decode_tick")),
        "chunk_spans": len(tr.spans("_run_prefill_chunk")),
        "op_names": sorted({o.name for o in tr.ops[tr.devices[0]]}),
        "op_stats": {o.name: {k: str(v)[:200] for k, v in o.stats.items()}
                     for o in tr.ops[tr.devices[0]][:60]},
        "host": sorted({h[0] for h in tr.host})[:200]}
    (out / "engine_tiny.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: summary[k] for k in ("bytes", "window_s", "busy_s",
                                              "decode_spans", "chunk_spans")}))


if __name__ == "__main__":
    main()
