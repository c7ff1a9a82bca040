"""Paper §3+§4 push-the-button pipeline: train a tiny LM, AMC-prune it to a
FLOPs target, then HAQ-quantize the pruned model under an edge latency
budget, and serve with the quantized Pallas kernels.

    PYTHONPATH=src python examples/compress_pipeline.py
"""
import sys

import jax
import numpy as np

sys.path.insert(0, "src")

from benchmarks.common import make_traced_policy_loss, trained_tiny_model
from repro.core import amc, haq
from repro.core.hardware_model import V5E_EDGE
from repro.core.quantization import make_quant_dot
from repro.configs import get_config
from repro.serving.engine import Engine, Request, derive_policy


def main():
    print("=== stage 0: train subject model (tiny granite) ===")
    model, params, val = trained_tiny_model("granite-3-8b", steps=80)
    eval_loss = jax.jit(lambda p: model.loss(p, val))
    base = float(eval_loss(params))
    print(f"base val loss: {base:.4f}")

    print("=== stage 1: AMC auto-pruning to 60% FLOPs ===")
    res_amc = amc.search(model, params, eval_loss,
                         amc.AMCConfig(target=0.6, episodes=16))
    layers = amc.enumerate_layers(model, tokens=4096)
    pruned = amc.apply_ratios(params, layers, res_amc["best"]["ratios"])
    print(f"AMC: loss {base:.4f} -> {res_amc['best']['loss']:.4f} at "
          f"{res_amc['best']['flops_frac']:.2f}x FLOPs "
          f"(ratios={['%.2f' % r for r in res_amc['best']['ratios']]})")

    print("=== stage 2: HAQ mixed-precision quantization (edge budget) ===")
    cfg_full = get_config("granite-3-8b")
    sites = haq.enumerate_sites(cfg_full, batch=1, seq=4096, decode=True)
    names = {s.name for s in sites}
    eval_policy = make_traced_policy_loss(model, pruned, val, names)
    res_haq = haq.search(cfg_full, sites, eval_policy,
                         haq.HAQConfig(episodes=12, budget_frac=0.55),
                         hw=V5E_EDGE)
    pol = res_haq["best"]["policy"]
    print(f"HAQ policy: { {k: v for k, v in pol.items()} }")
    print(f"HAQ: loss {res_haq['best']['loss']:.4f} at "
          f"{res_haq['best']['resource'] * 1e6:.1f}us "
          f"(budget {res_haq['best']['budget'] * 1e6:.1f}us)")

    print("=== stage 3: serve the compressed model (Pallas int kernels) ===")
    dot = make_quant_dot({k: v for k, v in pol.items()}, use_kernel=True)
    policy = derive_policy(model.cfg, V5E_EDGE, max_model_len=32,
                           param_bytes=model.param_bytes())
    req = Request(rid=0, prompt=np.ones(16, np.int32), max_new=8)
    toks = Engine(model, pruned, policy, dot=dot).run([req])[0]
    print("served tokens:", toks[16:])
    print("pipeline complete: prune -> quantize -> serve")


if __name__ == "__main__":
    main()
