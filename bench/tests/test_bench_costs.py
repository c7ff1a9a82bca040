"""Operation and byte counts against hand counts for the benchmark's
configurations (layers, widths and vocabulary as in their files)."""
import json
from pathlib import Path

import pytest

from bench import costs
from bench.families import dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name, **kw):
    m = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    return dict(m, **kw)


# (config, layers, per-layer matmul weights, unembedding weights,
#  attention ops per key per layer, K/V bytes per token and layer)
HAND = [
    # nemotron: q 6144x6144, k and v 6144x1024 each, o 6144x6144,
    # squared-ReLU MLP 2 x 6144x24576; head 6144x256000
    ("nemotron-4-15b.stage4", 8, 37748736 + 2 * 6291456 + 37748736
     + 2 * 150994944, 6144 * 256000, 4 * 48 * 128, 2 * 8 * 128 * 2),
    # granite: q 4096x4096, k and v 4096x1024, o 4096x4096, SwiGLU
    # 3 x 4096x12800; tied head 4096x49155
    ("granite-3-8b.stage2", 20, 16777216 + 2 * 4194304 + 16777216
     + 3 * 52428800, 4096 * 49155, 4 * 32 * 128, 2 * 8 * 128 * 2),
    # the same granite block at its published 40 layers (four-chip cell)
    ("granite-3-8b.stage2@40", 40, 199229440, 4096 * 49155, 16384, 4096),
]


@pytest.mark.parametrize("name,L,per_layer,head,attn,kv", HAND)
def test_hand_counts(name, L, per_layer, head, attn, kv):
    base, _, layers = name.partition("@")
    m = model(base, **({"num_layers": int(layers)} if layers else {}))
    assert dense.layer_matmul_params(m) == per_layer
    # one decode token at context 1000, one at 3000
    want = 2 * (2 * per_layer * L + 2 * head) + attn * L * (1000 + 3000)
    assert dense.decode_flops(m, [1000, 3000]) == want
    # a chunk of rows 1024..2047 (1024 rows; keys 1025..2048 per row)
    keys = sum(range(1025, 2049))
    assert dense.chunk_flops(m, 1024, 2048) == \
        2 * per_layer * L * 1024 + attn * L * keys
    assert dense.kv_bytes(m, 100) == kv * L * 100
    f, b = dense.chunk_attention_work(m, 1024, 2048)
    assert (f, b) == (attn * L * keys, kv * L * 2048)
    f, b = dense.decode_attention_work(m, [10, 20])
    assert (f, b) == (attn * L * 30, kv * L * 30)


def test_roofline_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, which = costs.roofline_seconds(197e12, 1, peaks)
    assert (t, which) == (1.0, "flops")
    t, which = costs.roofline_seconds(1, 819e9, peaks)
    assert (t, which) == (1.0, "bytes")
