"""The latent-attention + routed-experts model at a test's size, and the
same sizes as a configuration of the benchmark's mla_moe family (so the
program and the plain reference are built from one set of numbers)."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

#: a configuration of harness/families/mla_moe.py: router of 16, 8 held
TINY_CFG = dict(
    family="mla_moe", vocab_size=96, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    router_experts=16, experts_held=[0, 8], n_shared_experts=1,
    num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32, "type": "yarn"},
    torch_dtype="float32",
    deployment={"engine": dict(max_slots=2, page_size=8, max_seq_len=64,
                               prefill_chunk=8, max_new_tokens=8)})


def tiny_cfg(**over):
    return dict(TINY_CFG, **over)


def latent_model(seed=0, **over):
    """LatentMoEForCausalLM on the family's weights for ``seed``."""
    from harness.families import mla_moe

    return mla_moe.serving_model(tiny_cfg(**over), seed)
