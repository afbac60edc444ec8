"""The power iterations' work of a refresh step against counts written out
by hand."""
import json
import os

from chipbench.work import power_iter

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPT = {"name": "galore-sara-adam", "sara_pool_factor": 2,
       "svd_oversample": 1, "svd_power_iters": 2}


def test_power_iter_at_a_tiny_config():
    config = dict(family="dense", num_hidden_layers=1, hidden_size=8,
                  intermediate_size=16, num_attention_heads=2, num_key_value_heads=1, head_dim=4,
                  vocab_size=32, qkv_bias=False, tie_word_embeddings=False,
                  optimizer=OPT)
    # rank 2, pool 4, sketch 5: q, o (8, 8) and the MLP (8, 16) iterate
    # twice; k, v (4, 8) have a sketch of all 4 rows and skip them.
    # FLOPs 4 d n kp: 1280 (q, o), 2560 (MLP); bytes 4 (d n + 2 d kp):
    # 576 (q, o), 832 (MLP).
    assert sorted(power_iter.chains(config, rank=2)) == [
        (1, 4, 8, 4, 0), (1, 4, 8, 4, 0), (1, 8, 8, 5, 2), (1, 8, 8, 5, 2),
        (1, 8, 16, 5, 2), (1, 8, 16, 5, 2), (1, 8, 16, 5, 2)]
    flops, bytes_ = power_iter.per_step(config, rank=2)
    assert flops == 2 * 2 * 1280 + 3 * 2 * 2560 == 20480
    assert bytes_ == 2 * 2 * 576 + 3 * 2 * 832 == 7296


def test_power_iter_at_the_cell():
    with open(os.path.join(BENCH, "configs", "qwen2-1.5b.json")) as f:
        config = json.load(f)
    # 13 layers: q, o 26 slices of 1536 x 1536 and the MLP 39 of 1536 x
    # 8960 at a sketch of 2 x 384 + 8 = 776, two steps each; k, v (256
    # rows at rank 256) span their rows and skip them
    flops, _ = power_iter.per_step(config, rank=384)
    assert flops == 2 * 4 * 776 * 1536 * (26 * 1536 + 39 * 8960)
