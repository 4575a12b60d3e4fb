"""Operations one paged decode step needs (``lm_decode_step_paged``): the
weight matmuls for the live lanes, and attention over the tokens each
live lane can see, from the configuration's shapes."""


def matmul_params(c) -> int:
    """Weights a token meets in matmuls: every layer's projections and MLP,
    and the head (the embedding is a lookup)."""
    d, H, KVH, hd, f = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    layer = d * H * hd * 2 + d * KVH * hd * 2 + 3 * d * f
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def flops(c, lane_steps: int, visible: float) -> float:
    """``lane_steps``: live lanes summed over steps; ``visible``: tokens
    attended, summed over layers, lanes and steps.  QK and PV are two
    multiply-adds per head dimension and query head for each token."""
    return (2.0 * lane_steps * matmul_params(c)
            + 4.0 * c["num_attention_heads"] * c["head_dim"] * visible)
