"""A configuration, a traffic mix, a per-layer metric and a model family
added as new files and new entries only: the harness runs the new cell
and reports the new metric, with no edit to any file it already had."""
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


MOE_FAMILY = '''"""The mixture-of-experts family: the dense decoder with each layer's
feed-forward replaced by E SwiGLU experts under a float32 router (softmax
over every expert, the top k kept, their gates renormalised to sum to 1),
computed over every expert and weighted by the gates."""
import functools
import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench import weights as W
from bench.families import dense
from bench.reference import mm, q, rms


def leaf_specs(m):
    d, L = m["d_model"], m["num_layers"]
    E, f = m["moe"]["num_experts"], m["moe"]["d_ff_expert"]
    specs = {p: s for p, s in dense.leaf_specs(m).items() if "ffn" not in p}
    moe = dense.BLK + ("moe",)
    bf = "bfloat16"
    specs[moe + ("router",)] = W.Leaf((L, d, E), "float32", 1 / math.sqrt(d),
                                      True)
    specs[moe + ("w_in",)] = W.Leaf((L, E, d, f), bf, 1 / math.sqrt(d), True)
    specs[moe + ("w_gate",)] = W.Leaf((L, E, d, f), bf, 1 / math.sqrt(d),
                                      True)
    specs[moe + ("w_out",)] = W.Leaf((L, E, f, d), bf, 1 / math.sqrt(f), True)
    return specs


def experts(m, fp8, w, x):
    e, k = w["moe"], m["moe"]["experts_per_token"]
    h = q(rms(x, w["ln2"], m["norm_eps"]), -1, fp8)
    probs = jax.nn.softmax(mm("td,de->te", h, e["router"]), -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.einsum("tk,tke->te", top,
                      jax.nn.one_hot(idx, probs.shape[-1], dtype=R.F32))
    up = mm("td,edf->etf", h, q(e["w_in"], 1, fp8))
    g = mm("td,edf->etf", h, q(e["w_gate"], 1, fp8))
    y = mm("etf,efd->etd", q(jax.nn.silu(g) * up, -1, fp8),
           q(e["w_out"], 1, fp8))
    return x + mm("te,etd->td", gate, y)


def forward(m, specs, keys, xs, precision):
    fp8 = precision == "fp8"
    make = jax.jit(functools.partial(R.layer_weights, specs, dense.BLK))
    block = jax.jit(lambda w, x: experts(m, fp8, w,
                                         dense.attention(m, fp8, w, x)))
    for l in range(m["num_layers"]):
        w = make(R.layer_keys(keys, dense.BLK, l))
        xs = [block(w, x) for x in xs]
    return xs


def layer_matmul_params(m):
    e = m["moe"]
    return dense.layer_matmul_params(dict(m, d_ff=0)) \\
        + m["d_model"] * e["num_experts"] \\
        + e["experts_per_token"] * 3 * m["d_model"] * e["d_ff_expert"]


def decode_flops(m, contexts):
    per_tok = 2 * layer_matmul_params(m) * m["num_layers"] \\
        + 2 * m["d_model"] * m["vocab_size"]
    return sum(per_tok + dense.attention_flops(m, c) for c in contexts)


def chunk_flops(m, start, end):
    n = end - start
    return 2 * layer_matmul_params(m) * m["num_layers"] * n \\
        + dense.attention_flops(m, (start + 1 + end) * n // 2)


decode_attention_work = dense.decode_attention_work
chunk_attention_work = dense.chunk_attention_work
'''

MOE_READER = '''"""decode.token_flops: model operations of one decoded token apart from
its attention (the family's count at context 0)."""


def read(ctx):
    return ctx.family.decode_flops(ctx.model, [0])
'''

# 4 experts, 2 per token, capacity factor 4 / 2: an expert's capacity is
# every token of the call, so the program drops none
TINY_MOE = {"num_experts": 4, "experts_per_token": 2, "d_ff_expert": 32,
            "capacity_factor": 2.0}


def test_new_family_files_only(tmp_path):
    """A mixture-of-experts configuration enters as new files: its family
    module, its configuration, its cell and a reader of ``ctx.family``."""
    root = tinyroot.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "families" / "moe.py").write_text(MOE_FAMILY)
    (root / "bench" / "metrics" / "decode.token_flops.py").write_text(
        MOE_READER)
    cfg = tinyroot.tiny_config(family="moe", moe=TINY_MOE)
    (root / "bench" / "configs" / "tiny-moe.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "sparse experts"})
    bench["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                               "traffic": "tinychat", "chips": 1,
                               "why": "experts behind the paged path"})
    bench["per_layer"].append({
        "name": "decode.token_flops", "unit": "flop", "better": "lower",
        "source": "program_span", "layer": "model step",
        "moves": "itl_p95_ms", "workloads": ["tiny-moe.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    import time
    from bench import harness
    from repro.core.hardware_model import HARDWARES
    r = harness.run("tiny-moe.chat", 3, 2.0, True, time.monotonic(),
                    root=root, require_tpu=False,
                    hw=HARDWARES["v5e-1chip"], cache=False,
                    trace_dir=str(root / "trace"))
    assert r["correct"], r["checks"]
    m = cfg["model"]
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    attn = d * 4 * 16 + 2 * d * 2 * 16 + 4 * 16 * d
    want = 2 * (attn + d * 4 + 2 * 3 * d * 32) * L + 2 * d * V
    assert r["metrics"]["decode.token_flops"]["value"] == want
    assert "decode.mfu" in r["metrics"]
    for p, data in before.items():
        assert p.read_bytes() == data, p
