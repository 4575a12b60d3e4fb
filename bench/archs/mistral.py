"""Mistral's published configuration keys (``MistralForCausalLM``), read
for the program and for the work counts: SwiGLU MLP, RMSNorm, RoPE and
grouped-query attention with one head width for q, K and V, every layer
alike.  The counts come from the published keys, never from the program,
so a kernel's roofline reads the same work whatever implements it."""


def model_config(c, freeze):
    """The program's ``ModelConfig`` for the configuration file ``c``,
    with the freeze schedule ``freeze``."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], arch_type="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"], tie_embeddings=c["tie_word_embeddings"],
        source=c["source"], freeze=freeze)


def matmul_params(c) -> int:
    """Weights a decode token meets in matmuls: every layer's projections
    and MLP, and the head (the embedding is a lookup)."""
    d, H, KVH, hd, f = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    layer = d * H * hd * 2 + d * KVH * hd * 2 + 3 * d * f
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def attn_flops_per_token(c) -> int:
    """Attention FLOPs one layer spends on one visible token: QK and PV,
    two multiply-adds per head dimension and query head."""
    return 4 * c["num_attention_heads"] * c["head_dim"]


def kv_bytes_per_token(c, elem: int) -> int:
    """Bytes of one visible token's K and V in one layer, ``elem`` bytes
    an element."""
    return c["num_key_value_heads"] * c["head_dim"] * 2 * elem


def q_out_bytes_per_lane(c, elem: int) -> int:
    """Bytes of one lane's q in and attention output out, one call."""
    return 2 * c["num_attention_heads"] * c["head_dim"] * elem


def kv_scale_bytes_per_page(c) -> int:
    """Bytes of one page's K and V scales, float32 per KV head."""
    return 2 * c["num_key_value_heads"] * 4
