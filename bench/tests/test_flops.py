"""The Qwen2 family's FLOP and byte functions at the cells' shapes,
against sums worked out by hand from the published widths."""
import json
from pathlib import Path

import family

BENCH = Path(__file__).resolve().parents[1]
CONFIGS = BENCH / "configs"
qwen2 = family.of(BENCH, {"model_type": "qwen2"})


def shapes():
    cfg = json.loads((CONFIGS / "qwen1.5-32b.json").read_text())
    one = qwen2.shape(cfg)
    # the same model at 16 layers, tensor parallel over 4 chips
    tp = dict(cfg, num_hidden_layers=16, serving=dict(cfg["serving"], mp=4))
    return one, qwen2.shape(tp), qwen2.shape(tp, per_chip=True)


def test_layer_params():
    one, _, chip = shapes()
    # q 5120x5120 + k,v 2x5120x1024 + o 5120x5120 + 3 x 5120x27392
    assert qwen2.layer_matmul_params(one) == 483_655_680
    # per chip: q,o 5120x1280 each, k,v 5120x256 each, 3 x 5120x6848
    assert qwen2.layer_matmul_params(chip) == 120_913_920


def test_prefill_and_decode():
    one, tp, _ = shapes()
    # 4 x (2 x 483655680 x 2048 + 4 x 40 x 128 x 2048 x 2049 / 2)
    #   + 2 x 152064 x 5120
    assert qwen2.prefill_flops(one, 2048) == 8_097_654_374_400
    # 4 x (2 x 483655680 + 4 x 40 x 128 x 2049) + 2 x 152064 x 5120
    assert qwen2.decode_flops(one, 2049) == 5_594_234_880
    # 16 layers: 16 x (2 x 483655680 + 4 x 40 x 128 x 100) + head
    assert qwen2.decode_flops(tp, 100) == 17_066_885_120


def test_bytes():
    one, _, chip = shapes()
    # 2049 keys -> 129 pages of 16; K and V: 2 x 129 x 16 x 8 x 128 x 2 B;
    # q read and output written: 2 x 40 x 128 x 2 B
    assert qwen2.decode_bytes(one, [2049], 16) == 8_474_624
    # two slots on one chip of four: 2 x (1 + 3) pages x 16 x 2 x 128 x 2
    #   + 2 x 2 slots x 10 x 128 x 2
    assert qwen2.decode_bytes(chip, [16, 40], 16) == 75_776
