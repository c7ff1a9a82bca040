"""Record the engine-span trace fixture of test_bench_engine_spans.py on a
TPU.

    python3 bench/tests/record_span_fixture.py

The tiny dense cell of record_trace_fixture.py (head_dim 128, so the
Pallas paged-attention kernel tiles) is served through the engine on one
chip. Three ``Engine.step``s, each inside a ``bench.step`` annotation and
each running a prompt chunk and a decode tick, are traced with
jax.profiler; the engine's phase spans (``engine.step``,
``engine.decode.sample``, ...) land in the same trace. The ``.xplane.pb``
is written to chiprun_out/engine_tiny_spans.xplane.pb, with what
bench/xplane.py and bench/engine_spans.py read from it in
engine_tiny_spans.json."""
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import engine_spans, harness, xplane  # noqa: E402
from bench.paged_kernel import kernel_ops  # noqa: E402
from bench.tests import tinyroot  # noqa: E402
from bench.tests.record_trace_fixture import SHAPE  # noqa: E402
from repro.serving.engine import Request  # noqa: E402


def main():
    tmp = Path(tempfile.mkdtemp())
    root = tinyroot.make_root(tmp, **SHAPE)
    cell = harness.load_cell("tiny.chat", root)
    devices = harness.check_devices(1)
    engine, _ = harness.build(cell, 7, devices, harness.log)
    harness.warm_up(engine, SHAPE["vocab_size"])
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(2, SHAPE["vocab_size"], n).astype(
        np.int32)
    engine.submit(Request(rid=0, prompt=prompt(30), max_new=8))
    engine.step()
    tdir = str(tmp / "trace")
    jax.profiler.start_trace(tdir)
    for rid, n in enumerate((40, 200, 20), start=1):
        with jax.profiler.TraceAnnotation("bench.client"):
            engine.submit(Request(rid=rid, prompt=prompt(n), max_new=4))
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
    jax.profiler.stop_trace()
    src = xplane.newest(tdir)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    shutil.copy(src, out / "engine_tiny_spans.xplane.pb")
    tr = xplane.reduce(src)
    dev = tr.devices[0]
    summary = {
        "bytes": os.path.getsize(src), "window_s": tr.window_s,
        "busy_s": tr.busy_s, "devices": tr.devices,
        "device_ops": tr.device_ops(), "idle_gaps": tr.idle_gaps(),
        "spans": {n: len(engine_spans.intervals(tr, n)) for n in sorted(
            {h[0] for h in tr.host if h[0].startswith("engine.")
             and " " not in h[0]})},
        "kernel_ops": {
            n: sorted({xplane.op_label(o.name) for o in kernel_ops(
                tr.ops_within(dev, tr.spans(n)))})
            for n in ("engine.decode", "engine.chunk", "_decode_tick",
                      "_run_prefill_chunk")},
        "sample_ms": engine_spans.sample_ms(tr),
        "schedule_ms": engine_spans.schedule_ms(tr),
        "idle_sample_share": engine_spans.idle_sample_share(tr)}
    (out / "engine_tiny_spans.json").write_text(json.dumps(summary,
                                                           indent=1))
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
