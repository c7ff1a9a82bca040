"""Quickstart: build an assigned architecture, train it briefly on the
synthetic pipeline, checkpoint, restore, and serve a few tokens.

    PYTHONPATH=src python examples/quickstart.py [--arch gemma2-2b]
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_config, tiny_config
from repro.configs.base import OptimConfig, ShapeConfig, TrainConfig
from repro.core.hardware_model import V5E_EDGE
from repro.models.api import build_model
from repro.serving.engine import Engine, Request, derive_policy
from repro.training.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    cfg = tiny_config(args.arch)          # reduced same-family config (CPU)
    model = build_model(cfg)
    print(f"{cfg.name}: {model.param_count():,} params "
          f"(full config: {get_config(args.arch).param_count():,})")

    shape = ShapeConfig("quick", seq_len=64, global_batch=4, kind="train")
    tcfg = TrainConfig(optim=OptimConfig(lr=3e-3, total_steps=args.steps,
                                         warmup_steps=3),
                       checkpoint_dir="/tmp/repro_quickstart",
                       checkpoint_every=10, log_every=5)
    out = train(model, shape, tcfg, num_steps=args.steps)
    print(f"trained: loss {out['history'][0]['loss']} -> "
          f"{out['history'][-1]['loss']}")

    params = out["state"]["params"]
    policy = derive_policy(cfg, V5E_EDGE, max_model_len=32,
                           param_bytes=model.param_bytes())
    req = Request(rid=0, prompt=np.ones(16, np.int32), max_new=8)
    toks = Engine(model, params, policy).run([req])[0]
    print("generated:", toks[16:])


if __name__ == "__main__":
    main()
