"""The model family as a lookup: the dense module gives the parameter
layout and the counts that bench/weights.py and bench/costs.py gave before
the families were split out, an unknown family fails by the module it
lacks, and nested model settings become the program's dataclasses."""
import json
from pathlib import Path

import pytest

from bench import harness
from bench.families import dense
from bench.tests import tinyroot

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
BF, F32 = "bfloat16", "float32"

# (shape, dtype, std, stacked) per leaf, and the four counts at fixed
# arguments, as the layout and count functions gave them before the
# dense family had a module of its own
PARENT = {
    "nemotron-4-15b.stage4": (
        {("embed",): ((256000, 6144), BF, 1.0, False),
         ("final_norm",): ((6144,), F32, 0.1, False),
         ("lm_head",): ((6144, 256000), BF, 0.012757759076995721, False),
         ("blocks", "sub0", "ln1"): ((8, 6144), F32, 0.1, True),
         ("blocks", "sub0", "ln2"): ((8, 6144), F32, 0.1, True),
         ("blocks", "sub0", "attn", "wq"): (
             (8, 6144, 48, 128), BF, 0.012757759076995721, True),
         ("blocks", "sub0", "attn", "wk"): (
             (8, 6144, 8, 128), BF, 0.012757759076995721, True),
         ("blocks", "sub0", "attn", "wv"): (
             (8, 6144, 8, 128), BF, 0.012757759076995721, True),
         ("blocks", "sub0", "attn", "wo"): (
             (8, 48, 128, 6144), BF, 0.012757759076995721, True),
         ("blocks", "sub0", "ffn", "w_in"): (
             (8, 6144, 24576), BF, 0.012757759076995721, True),
         ("blocks", "sub0", "ffn", "w_out"): (
             (8, 24576, 6144), BF, 0.0063788795384978605, True)},
        29118824448, (3221275803648, 3050580738048),
        (958267392, 159711232),
        ((25820135424, 16777216), (160940163072, 65503232))),
    "granite-3-8b.stage2": (
        {("embed",): ((49408, 4096), BF, 0.015625, False),
         ("final_norm",): ((4096,), F32, 0.1, False),
         ("blocks", "sub0", "ln1"): ((20, 4096), F32, 0.1, True),
         ("blocks", "sub0", "ln2"): ((20, 4096), F32, 0.1, True),
         ("blocks", "sub0", "attn", "wq"): (
             (20, 4096, 32, 128), BF, 0.015625, True),
         ("blocks", "sub0", "attn", "wk"): (
             (20, 4096, 8, 128), BF, 0.015625, True),
         ("blocks", "sub0", "attn", "wv"): (
             (20, 4096, 8, 128), BF, 0.015625, True),
         ("blocks", "sub0", "attn", "wo"): (
             (20, 32, 128, 4096), BF, 0.015625, True),
         ("blocks", "sub0", "ffn", "w_in"): (
             (20, 4096, 12800), BF, 0.015625, True),
         ("blocks", "sub0", "ffn", "w_out"): (
             (20, 12800, 4096), BF, 0.008838834764831844, True),
         ("blocks", "sub0", "ffn", "w_gate"): (
             (20, 4096, 12800), BF, 0.015625, True)},
        26712678400, (4123252490240, 3957962833920),
        (1597112320, 399278080),
        ((43033559040, 41943040), (268233605120, 163758080))),
}
CONTEXTS = [1, 777, 4096]
CHUNKS = [(0, 512), (1536, 1999)]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_family_as_before(name):
    m = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    specs, decode, chunks, decode_work, chunk_work = PARENT[name]
    got = {p: (s.shape, s.dtype, s.std, s.stacked)
           for p, s in dense.leaf_specs(m).items()}
    assert got == specs
    assert dense.decode_flops(m, CONTEXTS) == decode
    assert tuple(dense.chunk_flops(m, *c) for c in CHUNKS) == chunks
    assert dense.decode_attention_work(m, CONTEXTS) == decode_work
    assert tuple(dense.chunk_attention_work(m, *c) for c in CHUNKS) == \
        chunk_work


def test_unknown_family_names_its_module(tmp_path):
    root = tinyroot.make_root(tmp_path, family="nosuch")
    with pytest.raises(SystemExit, match="bench/families/nosuch.py"):
        harness.load_cell("tiny.chat", root)


def test_nested_settings_become_dataclasses():
    from repro.configs.base import MoEConfig, SSMConfig
    moe = {"num_experts": 8, "experts_per_token": 2, "d_ff_expert": 96,
           "capacity_factor": 4.0}
    ssm = {"d_state": 16, "head_dim": 32, "n_groups": 1}
    conf = tinyroot.tiny_config(family="moe", moe=moe, ssm=ssm)
    cfg = harness.model_config(conf)
    assert cfg.moe == MoEConfig(**moe)
    assert cfg.ssm == SSMConfig(**ssm)
    assert cfg.attn_pattern == ("global",)
    hash(cfg)


def test_nested_settings_overlay_the_arch():
    """A partial nested dict keeps what the arch's own nested object sets:
    llama4-maverick interleaves MoE with dense layers (every 2, from 1)."""
    from repro.configs import get_config
    arch = get_config("llama4-maverick-400b-a17b").moe
    moe = {"num_experts": 8, "experts_per_token": 1, "d_ff_expert": 96}
    conf = dict(tinyroot.tiny_config(family="moe", moe=moe),
                arch="llama4-maverick-400b-a17b")
    cfg = harness.model_config(conf)
    assert (cfg.moe.every, cfg.moe.offset) == (arch.every, arch.offset) \
        == (2, 1)
    assert (cfg.moe.num_experts, cfg.moe.d_ff_expert) == (8, 96)
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(1)


@pytest.mark.parametrize("model,key", [
    ({"moe": {"num_experts": 8, "experts_per_token": 2, "d_ff_expert": 96,
              "top_k": 2}}, "model.moe.top_k"),
    ({"ssm": {"d_state": 16, "state_size": 16}}, "model.ssm.state_size"),
    ({"num_layer": 2}, "model.num_layer"),
])
def test_unknown_key_is_named(model, key):
    with pytest.raises(SystemExit, match=key.replace(".", r"\.")):
        harness.model_config(tinyroot.tiny_config(**model))
