"""The benchmark's work functions against counts written out by hand."""
import pytest

from chipbench.families import dense
from chipbench.work import flash_attention, optimizer_update


def _config(layers, d, f, h, kvh, hd, v, **kw):
    return dict(family="dense", num_hidden_layers=layers, hidden_size=d,
                intermediate_size=f, num_attention_heads=h,
                num_key_value_heads=kvh, head_dim=hd, vocab_size=v,
                qkv_bias=False, tie_word_embeddings=False, **kw)


# published widths, full depth
QWEN2_1_5B = _config(28, 1536, 8960, 12, 2, 128, 151936)
GRANITE_8B = _config(36, 4096, 14336, 32, 8, 128, 49152)


@pytest.mark.parametrize("config, want", [
    # 28 layers x (q, o: 1536 x 1536; k, v: 1536 x 256; gate, up, down:
    # 1536 x 8960) + head 1536 x 151936
    (QWEN2_1_5B, 28 * (2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960)
     + 1536 * 151936),
    # 36 x (q, o: 4096^2; k, v: 4096 x 1024; MLP 3 x 4096 x 14336)
    # + head 4096 x 49152
    (GRANITE_8B, 36 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
     + 4096 * 49152),
])
def test_matrix_params(config, want):
    assert dense.matrix_params(config) == want


@pytest.mark.parametrize("config, want", [
    # 6 x 1,543,569,408 matrix parameters + 6 x 28 layers x 4096 x 1536
    (QWEN2_1_5B, 6 * 1_543_569_408 + 6 * 28 * 4096 * 1536),
    # 6 x 8,053,063,680 + 6 x 36 x 4096 x 4096
    (GRANITE_8B, 6 * 8_053_063_680 + 6 * 36 * 4096 * 4096),
])
def test_flops_per_token(config, want):
    assert dense.flops_per_token(config, 4096) == want
    assert want in (10_318_381_056, 51_942_260_736)


def test_optimizer_update_at_a_tiny_bucket():
    config = _config(1, 8, 16, 2, 1, 4, 32)
    # oriented (d, n) at rank min(2, d): q, o (8, 8); k, v (4, 8);
    # gate, up, down (8, 16).  Bytes per slice 4 (3 d n + d r + 4 r n):
    # 4 x 272 = 1088 (q, o), 4 x 168 = 672 (k, v), 4 x 528 = 2112 (MLP).
    # FLOPs per slice 2 x 2 d n r: 512, 256, 1024.
    flops, bytes_ = optimizer_update.per_step(config, rank=2)
    assert bytes_ == 2 * 1088 + 2 * 672 + 3 * 2112 == 9856
    assert flops == 2 * 512 + 2 * 256 + 3 * 1024 == 4608


def test_flash_attention_per_call():
    config = _config(1, 8, 16, 2, 1, 4, 32)
    flops, bytes_ = flash_attention.per_call(config, batch=1, seq_len=16)
    # QK^T and PV over half of 16 x 16 scores, 2 heads of 4: 2 x 2 x 16^2
    # x 4 x 2 / 2; Q and O (2 heads), K and V (1 head) in bf16
    assert flops == 4096
    assert bytes_ == 2 * 16 * 4 * (2 + 2 + 1 + 1)

