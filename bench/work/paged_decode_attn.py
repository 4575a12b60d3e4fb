"""What the paged decode attention kernel (``kernels/paged_decode_attn.py``)
needs for its calls: the K and V of the tokens it can see, q and the
output, and the per-page tables; not the bytes the kernel happens to
fetch.  Attention is memory bound at these shapes (H/KVH multiply-adds per
byte of K/V), so the bound is reported with the share."""

# the kernel's op in a device trace: the Pallas call's custom-call takes
# the kernel function's name (``paged_decode_attention_kernel.<n>``)
OP = r"paged_decode_attention"


def work(c, *, visible: float, calls: int, lanes: int, pages: int,
         page: int, kv_bytes: int = 2) -> dict:
    """``visible``: tokens attended summed over calls and lanes; ``calls``
    kernel invocations (one per layer and step), each over ``lanes`` lanes
    of ``pages`` physical page slots of ``page`` tokens."""
    H, KVH, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = 4.0 * H * hd * visible
    kv = visible * KVH * hd * 2 * kv_bytes
    per_call = (2 * lanes * H * hd * kv_bytes          # q in, output out
                + lanes * pages * 4                     # relevance out
                + lanes * pages * (4 + 1 + 4)           # table, visible, quant
                + lanes * pages * page                  # slot mask
                + lanes * pages * 2 * KVH * 4)          # K/V scales
    return {"flops": flops, "bytes": kv + calls * per_call}


def least_seconds(w: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of the two roofline times."""
    t_c = w["flops"] / peaks["bf16_flops_per_s"]
    t_m = w["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
