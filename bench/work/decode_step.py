"""Operations one paged decode step needs (``lm_decode_step_paged``): the
weight matmuls for the live lanes, and attention over the tokens each
live lane can see, from the counts the configuration's architecture file
(``bench/archs/<architecture>.py``) gives."""
from harness import arch


def matmul_params(c) -> int:
    """Weights a token meets in matmuls: every layer's projections and MLP,
    and the head (the embedding is a lookup)."""
    return arch(c).matmul_params(c)


def flops(c, lane_steps: int, visible: float) -> float:
    """``lane_steps``: live lanes summed over steps; ``visible``: tokens
    attended, summed over layers, lanes and steps.  Two FLOPs per weight
    and lane, and the attention FLOPs of each visible token."""
    return (2.0 * lane_steps * matmul_params(c)
            + arch(c).attn_flops_per_token(c) * visible)
