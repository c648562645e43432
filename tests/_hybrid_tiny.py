"""The gated-delta hybrid model at a test's size (h 64, two periods of
three linear layers and a full one, 4 heads over 2 K/V heads, key 8 /
value 16), and the same sizes as a configuration of the benchmark's
gated_delta_hybrid family (so the program and the plain reference are
built from one set of numbers)."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY_CFG = dict(
    family="gated_delta_hybrid", vocab_size=96, hidden_size=64,
    intermediate_size=96, num_hidden_layers=8, layer_types=PERIOD * 2,
    num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, rms_norm_eps=1e-6,
    rope_parameters={"rope_theta": None}, torch_dtype="float32",
    deployment={"engine": dict(max_slots=3, page_size=8, max_seq_len=64,
                               prefill_chunk=8, max_new_tokens=8)})


def tiny_cfg(**over):
    return dict(TINY_CFG, **over)


def hybrid_model(seed=0, **over):
    """GatedDeltaHybridForCausalLM on the family's weights for ``seed``."""
    from harness.families import gated_delta_hybrid

    return gated_delta_hybrid.serving_model(tiny_cfg(**over), seed)
