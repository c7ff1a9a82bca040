"""A checkout-like directory holding a tiny dense cell, for CPU tests of
the harness: bench/ copied whole, plus a tiny configuration, a small chat
mix, and a BENCHMARK.json that names them."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {
    "family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
    "activation": "swiglu", "attn_pattern": ["global"], "rope_theta": 10000.0,
    "norm_eps": 1e-05, "tie_embeddings": False, "scale_embeddings": False,
    "sandwich_norm": False, "attn_softcap": 0.0, "logit_softcap": 0.0,
    "dtype": "bfloat16"}
TINY_MIX = {"loop": "closed", "clients": "max_batch",
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                       "min": 8, "max": 150},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 32}}
# bf16 serving against the float32 reference at these widths reads widest
# gaps of a few thousandths (CPU); a wrong token reads tens.
TINY_LIMIT = 0.05


def tiny_config(**model):
    return {"name": "tiny", "arch": "granite-3-8b",
            "model": dict(TINY_MODEL, **model),
            "serving": {"max_model_len": 256, "max_batch_cap": 4,
                        "hbm_util": 0.9, "page_size": 16,
                        "decode_slo_s": 0.03},
            "check": {"max_logit_gap": TINY_LIMIT}}


def make_root(tmp: Path, **model) -> Path:
    """tmp/ with bench/ and a BENCHMARK.json whose one cell is tiny.chat."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config(**model)))
    (tmp / "bench" / "traffic" / "tinychat.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny dense decoder for CPU tests"}]
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                           "traffic": "tinychat", "chips": 1,
                           "why": "tiny closed loop for CPU tests"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, seconds: float = 2.0, trace: bool = False, seed: int = 5,
        **kw):
    import time
    from bench import harness
    from repro.core.hardware_model import HARDWARES
    return harness.run("tiny.chat", seed, seconds, trace, time.monotonic(),
                       root=root, require_tpu=False,
                       hw=HARDWARES["v5e-1chip"], cache=False,
                       trace_dir=str(root / "trace"), **kw)
