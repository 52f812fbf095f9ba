"""``bench/counts.py`` against arithmetic done by hand for one dense and one
MoE step, a family that states none, and the full-size configurations'
counts pinned."""
import pytest

from bench import counts, harness

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


# (FLOPs, bytes) of a step at full size, as the counts read before each
# family's blocks were counted by its own reference module
PINNED = {
    ("qwen2.5-3b", 1, 1): (6171688960.0, 6172218112.0),
    ("qwen2.5-3b", 1, 228): (6238633984.0, 6180586240.0),
    ("qwen2.5-3b", 1, 1024): (6473383936.0, 6209929984.0),
    ("qwen2.5-3b", 8, 1): (49373511680.0, 6174603264.0),
    ("qwen2.5-3b", 8, 228): (49909071872.0, 6241548288.0),
    ("qwen2.5-3b", 8, 1024): (51787071488.0, 6476298240.0),
    ("qwen2.5-3b", 16, 1): (98747023360.0, 6177329152.0),
    ("qwen2.5-3b", 16, 228): (99818143744.0, 6311219200.0),
    ("qwen2.5-3b", 16, 1024): (103574142976.0, 6780719104.0),
    ("deepseek-moe-16b-8L", 1, 1): (1797324800.0, 1797603328.0),
    ("deepseek-moe-16b-8L", 1, 228): (1812201472.0, 1812480000.0),
    ("deepseek-moe-16b-8L", 1, 1024): (1864368128.0, 1864646656.0),
    ("deepseek-moe-16b-8L", 8, 1): (14378598400.0, 5797116634.827881),
    ("deepseek-moe-16b-8L", 8, 228): (14497611776.0, 5916130010.827881),
    ("deepseek-moe-16b-8L", 8, 1024): (14914945024.0, 6333463258.827881),
    ("deepseek-moe-16b-8L", 16, 1): (28757196800.0, 7995943644.386246),
    ("deepseek-moe-16b-8L", 16, 228): (28995223552.0, 8233970396.386246),
    ("deepseek-moe-16b-8L", 16, 1024): (29829890048.0, 9068636892.386246),
}


@pytest.mark.parametrize("config,batch,filled", sorted(PINNED))
def test_full_size_counts_are_pinned(config, batch, filled):
    model = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    assert counts.step_counts(model, batch, filled) == PINNED[
        config, batch, filled]


def test_a_family_without_counts_is_refused(family_dir):
    (family_dir / "uncounted.py").write_text(
        "from .dense import MODEL_KEYS, TEST_CUT, forward, layout\n")
    cfg = dict(harness.load_json(harness.BENCH / "configs"
                                 / "qwen2.5-3b.json"), family="uncounted")
    with pytest.raises(ValueError, match="uncounted"):
        counts.step_counts(cfg, 1, 1)
