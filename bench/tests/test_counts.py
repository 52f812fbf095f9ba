"""``bench/counts.py`` against arithmetic done by hand for one dense and one
MoE step."""
import pytest

from bench import counts

PEAK = {"bf16_flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}


def test_dense_step():
    cfg = {"family": "dense", "hidden_size": 4, "num_hidden_layers": 1,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "intermediate_size": 8, "vocab_size": 10,
           "tie_word_embeddings": True, "qkv_bias": True}
    # head dim 2; q and o: 4*2*2 each, k and v: 4*1*2 each -> 48
    attn = 2 * 4 * 2 * 2 + 2 * 4 * 1 * 2
    mlp = 3 * 4 * 8                       # 96
    head = 4 * 10                         # tied: the embedding table
    # batch 2 over 3 filled positions: q.k and p.v, 2 heads of 2
    flops = 2 * 2 * (attn + mlp + head) + 2 * 2 * 2 * 2 * 2 * 3
    bias, norms = (2 + 2 * 1) * 2, 2 * 4
    weights = attn + bias + norms + mlp + 4 + head     # + final norm
    kv = 2 * 2 * 1 * 2 * 3                # k and v, batch 2, 1 head of 2
    logits = 2 * 10
    assert counts.step_counts(cfg, 2, 3) == (flops, 2 * (weights + kv
                                                         + logits))
    assert flops == 832 and 2 * (weights + kv + logits) == 496
    assert counts.least_seconds(cfg, 2, 3, PEAK) == pytest.approx(4.96)


def test_moe_step():
    cfg = {"family": "moe", "hidden_size": 4, "num_hidden_layers": 1,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "moe_intermediate_size": 3, "n_routed_experts": 4,
           "num_experts_per_tok": 2, "n_shared_experts": 1,
           "vocab_size": 10, "tie_word_embeddings": False}
    attn = 4 * 4 * 2 * 2                  # q, k, v, o: 64
    expert = 3 * 4 * 3                    # 36
    router = 4 * 4
    # 2 tokens, 2 of 4 experts each: 4 * (1 - (1/2)^2) = 3 experts hit
    read = router + expert + 3 * expert
    used = router + expert + 2 * expert
    head = 4 * 10
    flops = 2 * 2 * (attn + used + head) + 2 * 2 * 2 * 2 * 2 * 1
    weights = attn + 2 * 4 + read + 4 + head + 2 * 4   # + embedding rows
    kv = 2 * 2 * 2 * 2 * 1
    logits = 2 * 10
    assert counts.step_counts(cfg, 2, 1) == (flops, 2 * (weights + kv
                                                         + logits))
    assert flops == 944 and 2 * (weights + kv + logits) == 640
