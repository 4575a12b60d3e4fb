"""Pallas TPU kernel: paged flash-decode attention over the bounded active
page pool — the serving hot path of the PagedContinuousEngine.

Grid walks (batch, physical page).  The wrapper folds each lane's page
table, per-page visibility mask and slot mask into one `live` flag per
(lane, slot) that arrives via scalar prefetch (SMEM), so the kernel knows
*before* touching VMEM whether the slot it was scheduled on is mapped and
attendable.  Unmapped slots (page_table < 0), invisible pages (frozen and
not thawed by the recovery ladder — page_visible == 0) and pages whose
slot mask is empty skip their MXU work entirely under `pl.when` —
mirroring `freeze_decode_attn`'s block skip, but page-granular and per
lane.  The page-mean |Q.K| relevance is emitted fused, feeding the
page-granular freeze schedule (core.paging.page_freeze_update); a page the
entropy ladder just thawed re-enters both the softmax and the relevance
accounting through the same flag, so the freeze schedule immediately sees
fresh scores for it.

Mosaic lays out the last two dims of every block and value in (8, 128)
tiles, so the kernel keeps all of them 2-D: a page is read as
(page * KVH, hd) rows, every query head scores every row and keeps its own
kv head's rows, and the small per-page operands (slot mask, scales,
relevance) carry unit axes so that their blocks' last two dims are whole.
tests/test_tpu_compile.py compiles it for a described v5e.

On real TPU the page pool lives in HBM while the frozen store is in host
memory; the kernel only ever touches the device pool — the bounded-memory
guarantee of DESIGN.md §2.  Validated on CPU with interpret=True against
kernels.ref.paged_decode_attention_ref (tests/test_kernels.py sweep).

The scalar-prefetched skip doubles as the async DMA pipeline's
**staging-slot visibility** guarantee: the serving engine reserves extra
physical slots per lane and speculatively uploads likely-thaw pages into
them while their page-table entries are still -1, so the pool carries
live K/V the sequence must not yet attend.  Because `live` is read from
SMEM before any VMEM access, a staged slot costs zero MXU work and zero
relevance until the host remaps it — at which point the same prefetch
path makes it attendable with no kernel change
(tests/test_async_pipeline.py::TestStagingSlotVisibility).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(live_ref,                     # SMEM scalar prefetch: (B, P) i32
            qt_ref,                       # SMEM scalar prefetch: (B, P) i32
            q_ref, k_ref, v_ref, sc_ref, mask_ref,
            o_ref, rel_ref,
            m_ref, l_ref, acc_ref,
            *, kv_heads: int, scale: float):
    b = pl.program_id(0)
    blk = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[b, blk] != 0)
    def _page():
        q = q_ref[0]                                   # (H, hd)
        H, hd = q.shape
        page = k_ref.shape[2]
        n = page * kv_heads
        # the page as (page*KVH, hd) rows; row j is token j // KVH of kv
        # head j % KVH.  Every query head scores every row and keeps the
        # rows of its own kv head: all values stay 2-D, as Mosaic wants.
        k = k_ref[0, 0].reshape(n, hd)
        v = v_ref[0, 0].reshape(n, hd).astype(jnp.float32)
        tok = mask_ref[0, 0] != 0                      # (1, n)
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, n), 0) // (H // kv_heads)
        col_kv = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1) % kv_heads
        own = (row_kv == col_kv) & tok                 # (H, n)
        # in-kernel dequant of quantized (frozen/thawed) pages: the pool
        # holds the integer-valued payload in the pool dtype, the per-page
        # per-kv-head scales (repeated per query head) ride next to the
        # page table.  Hot pages carry quant flag 0 and multiply by exactly
        # 1.0 — bitwise identity, so kv_quant="none" stays bit-identical
        # to the unquantized kernel.
        quant = qt_ref[b, blk] != 0
        sc = sc_ref[0, 0]                              # (H, 2)
        sk = jnp.where(quant, sc[:, 0:1], 1.0)         # (H, 1)
        sv = jnp.where(quant, sc[:, 1:2], 1.0)
        dt = jnp.promote_types(q.dtype, k.dtype)      # bf16 products are exact
        raw = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sk   # (H, n)
        # page relevance: mean over query heads and live tokens of |Q.K|
        rel = jnp.sum(jnp.where(own, jnp.abs(raw), 0.0), axis=1, keepdims=True)
        rel = jnp.sum(rel, axis=0, keepdims=True)      # (1, 1)
        n_tok = jnp.sum(tok.astype(jnp.float32), axis=1, keepdims=True)
        rel_ref[0, 0] = rel * kv_heads / (H * n_tok)
        s = jnp.where(own, raw * scale, NEG_INF)
        m_prev = m_ref[...]                            # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(own, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.dot(p * sv, v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(live_ref[b, blk] == 0)
    def _skip():
        # unmapped slot, invisible (frozen, un-thawed) page, or empty slot
        # mask: no MXU work, relevance 0
        rel_ref[0, 0] = jnp.zeros((1, 1), rel_ref.dtype)

    @pl.when(blk == nblk - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / jnp.maximum(l, 1e-30)
        o = jnp.where(l > 0, o, 0.0)
        o_ref[0] = o.astype(o_ref.dtype)


def paged_decode_attention_kernel(
    q: jnp.ndarray,           # (B, H, hd)
    k_pages: jnp.ndarray,     # (B, P, page, KVH, hd)
    v_pages: jnp.ndarray,
    slot_mask: jnp.ndarray,   # (B, P, page) bool
    page_table: Optional[jnp.ndarray] = None,   # (B, P) i32; < 0 = unmapped
    page_visible: Optional[jnp.ndarray] = None, # (B, P) bool; False = frozen
    page_quant: Optional[jnp.ndarray] = None,   # (B, P) i32; != 0 = quantized
    kv_scales: Optional[jnp.ndarray] = None,    # (B, P, 2, KVH) f32
    *,
    interpret: bool = False,
):
    """Returns (out (B, H, hd), page_relevance (B, P) f32).

    ``page_visible`` is the recovery ladder's thaw-aware mask (``~frozen``
    after in-step un-freezing): False pages skip their MXU work exactly
    like unmapped slots.  None means all mapped pages are visible.

    ``page_quant`` / ``kv_scales`` are the per-page quantization slots
    (core/quant.py): where the flag is non-zero the pool holds an
    integer-valued payload and the kernel multiplies K by
    ``kv_scales[b, p, 0]`` and V by ``kv_scales[b, p, 1]`` (per kv-head)
    after the load.  None (or an all-zero flag array) multiplies by 1.0
    exactly — bit-identical to the unquantized kernel.
    """
    B, H, hd = q.shape
    _, P, page, KVH, _ = k_pages.shape
    n = page * KVH
    scale = 1.0 / math.sqrt(hd)
    # one SMEM flag per (lane, slot): mapped, visible and holding at least
    # one attendable token.  The page table is read only as "mapped".
    live = jnp.any(slot_mask, -1)
    if page_table is not None:
        live = live & (jnp.asarray(page_table) >= 0)
    if page_visible is not None:
        live = live & jnp.asarray(page_visible).astype(bool)
    if page_quant is None:
        page_quant = jnp.zeros((B, P), jnp.int32)
    if kv_scales is None:
        kv_scales = jnp.ones((B, P, 2, KVH), jnp.float32)
    # small per-page operands get the kernel's row layout, each with its
    # last two dims whole so the blocks meet Mosaic's tiling rule: the
    # slot mask repeated per kv head (row j of a page = token j // KVH),
    # the scales repeated per query head.
    tok_mask = jnp.repeat(slot_mask.astype(jnp.int32), KVH, axis=-1)
    tok_mask = tok_mask.reshape(B, P, 1, n)
    head_scales = jnp.repeat(jnp.asarray(kv_scales, jnp.float32), H // KVH,
                             axis=-1).swapaxes(-1, -2)      # (B, P, H, 2)

    # index maps receive the scalar-prefetch refs as trailing arguments
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, p, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, page, KVH, hd), lambda b, p, *_: (b, p, 0, 0, 0)),
            pl.BlockSpec((1, 1, page, KVH, hd), lambda b, p, *_: (b, p, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, 2), lambda b, p, *_: (b, p, 0, 0)),
            pl.BlockSpec((1, 1, 1, n), lambda b, p, *_: (b, p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, hd), lambda b, p, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, p, *_: (b, p, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    out, rel = pl.pallas_call(
        functools.partial(_kernel, kv_heads=KVH, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            jax.ShapeDtypeStruct((B, P, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(live.astype(jnp.int32), jnp.asarray(page_quant, jnp.int32),
      q, k_pages, v_pages, head_scales, tok_mask)
    return out, rel.reshape(B, P)
