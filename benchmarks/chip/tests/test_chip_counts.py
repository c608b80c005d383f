"""Model FLOPs and kernel bytes against counts made by hand."""
import pytest

import counts
import dims

DS = dims.load("deepseek-7b")
# Qwen3-30B-A3B's published widths at 4 of 48 layers, for the expert
# layer's counts
QW = dims.Dims(name="qwen3-moe-30b-a3b", program="qwen3-moe-30b-a3b",
               layers=4, d_model=2048, heads=32, kv_heads=4, head_dim=128,
               d_ff=768, vocab=151936, experts=128, top_k=8, qk_norm=True,
               rope_theta=1e6)


def test_deepseek_forward_per_token():
    # q, k, v, o: 4 · 4096²; gate, up, down: 3 · 4096 · 11008
    assert counts.proj_flops(DS) == 2 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
    # LoRA r=8 on q and v: (4096·8 + 8·4096) each
    assert counts.lora_flops(DS) == 2 * 2 * (2 * 4096 * 8)
    assert counts.head_flops(DS) == 2 * 4096 * 102400


def test_qwen3_forward_per_token_counts_top8_and_router():
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    experts = 8 * 3 * 2048 * 768
    router = 2048 * 128
    assert counts.proj_flops(QW) == 2 * (attn + experts + router)
    assert counts.lora_flops(QW) == 2 * (8 * (2048 + 4096) + 8 * (2048 + 512))


def test_causal_attention_counts_the_kept_half():
    # one head of width 128, 4 tokens: 10 kept pairs, QKᵀ and PV
    d = dims.Dims("t", "deepseek-7b", 1, 128, 1, 1, 128, 1, 8)
    assert counts.attn_flops(d, 4) == 2 * 2 * 128 * 10


def test_deepseek_training_step():
    T, L = 4096, 16
    fwd = L * T * counts.proj_flops(DS) + T * counts.head_flops(DS)
    qkv = T * 2 * 4096 * 3 * 4096
    lora = 3 * L * T * counts.lora_flops(DS)
    attn = 3 * L * 2 * (2 * 2 * 32 * 128 * 2048 * 2049 / 2)
    assert counts.train_flops(DS, 2, 2048) == pytest.approx(
        2 * fwd - qkv + lora + attn)
    # ≈ 4 · 3.66e9 matmul parameters · 4096 tokens + attention
    assert counts.train_flops(DS, 2, 2048) == pytest.approx(6.29e13,
                                                            rel=2e-3)


def test_qwen3_training_step():
    assert counts.train_flops(QW, 2, 2048) == pytest.approx(9.57e12,
                                                            rel=2e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
