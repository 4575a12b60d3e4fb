"""What the paged decode attention kernel (``kernels/paged_decode_attn.py``)
needs for its calls: the K and V of the tokens it can see, q and the
output, and the per-page tables; not the bytes the kernel happens to
fetch.  Attention is memory bound at these shapes (H/KVH multiply-adds per
byte of K/V), so the bound is reported with the share.  The per-token and
per-lane counts come from the configuration's architecture file
(``bench/archs/<architecture>.py``)."""
from harness import arch

# the kernel's op in a device trace: the Pallas call's custom-call takes
# the kernel function's name (``paged_decode_attention_kernel.<n>``)
OP = r"paged_decode_attention"


def work(c, *, visible: float, calls: int, lanes: int, pages: int,
         page: int, kv_bytes: int = 2) -> dict:
    """``visible``: tokens attended summed over calls and lanes; ``calls``
    kernel invocations (one per layer and step), each over ``lanes`` lanes
    of ``pages`` physical page slots of ``page`` tokens."""
    a = arch(c)
    flops = a.attn_flops_per_token(c) * visible
    kv = visible * a.kv_bytes_per_token(c, kv_bytes)
    per_call = (lanes * a.q_out_bytes_per_lane(c, kv_bytes)  # q in, out
                + lanes * pages * 4                     # relevance out
                + lanes * pages * (4 + 1 + 4)           # table, visible, quant
                + lanes * pages * page                  # slot mask
                + lanes * pages * a.kv_scale_bytes_per_page(c))  # K/V scales
    return {"flops": flops, "bytes": kv + calls * per_call}


def least_seconds(w: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of the two roofline times."""
    t_c = w["flops"] / peaks["bf16_flops_per_s"]
    t_m = w["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
