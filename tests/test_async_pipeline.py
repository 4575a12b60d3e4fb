"""Async host<->device DMA pipeline (serving/dma.py + engine integration):

* token-level parity between the async and synchronous pipelines on a
  mixed thaw/rewind trace (the pipeline must be a pure overlap
  optimization — same decisions, same order, different wall-clock),
* the transfer-op regression: non-boundary decode steps issue ZERO
  blocking host transfers (the async pipeline's defining property),
* speculative-thaw staging: staged pages install as metadata-only remaps
  (no K/V push) with a device-side copy, and the reserved staging slots
  leave the in-step freeze dynamics bit-identical to a plain pool,
* kernel contract: a staging slot full of garbage K/V is invisible to
  paged attention while its page table entry is unmapped.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving.dma import FetchRing, HostStaging, TransferStats
from repro.serving.engine import ContinuousEngine, PagedContinuousEngine
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = get_config("llama3-8b-tiny")
    fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                             recovery_enabled=False)
    cfg = dataclasses.replace(cfg, freeze=fc, dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def thaw_rewind_cfg(tiny_f32):
    """Aggressive freeze pressure + low entropy thresholds: pages stash,
    FR thaws fire, and RR rewinds trigger (the mixed trace of the parity
    requirement)."""
    cfg, _ = tiny_f32
    fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                             tau_mode="quantile", quantile=0.6, k_soft=0.7,
                             recovery_enabled=True,
                             entropy_abs_threshold=0.5, rewalk_tokens=6)
    cfg = dataclasses.replace(cfg, freeze=fc)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(eng, cfg, lens, seed=0):
    s = Scheduler(eng)
    rng = np.random.RandomState(seed)
    uids = [s.submit(rng.randint(0, cfg.vocab_size, size=pl), n,
                     SamplingParams.greedy())
            for pl, n in lens]
    s.run()
    return [s.done[u] for u in uids]


class TestParityAsyncVsSync:
    def test_paged_thaw_rewind_trace(self, thaw_rewind_cfg):
        """Sync and async paged engines over a trace that exercises the
        full recovery surface (stash, FR thaw, RR rewind) must emit
        identical tokens AND identical per-request telemetry — and the
        trace must actually thaw and rewind or the test is vacuous."""
        cfg, params = thaw_rewind_cfg
        lens = [(48, 70), (20, 50)]

        def run(async_pipeline):
            eng = PagedContinuousEngine(
                cfg, params, max_seq=256, n_lanes=2, max_active_pages=6,
                prefill_chunk=16, rewind_cooldown=12,
                async_pipeline=async_pipeline, burst_prefill=False)
            return eng, _serve(eng, cfg, lens)

        se, sync_done = run(False)
        ae, async_done = run(True)
        assert se.ctl.n_thaw > 0, "no thaw fired — parity test is vacuous"
        assert sum(r.telemetry.rewinds for r in sync_done) > 0, \
            "no rewind fired — parity test is vacuous"
        assert ae.ctl.n_thaw == se.ctl.n_thaw
        for a, b in zip(sync_done, async_done):
            np.testing.assert_array_equal(a.result, b.result)
            assert a.telemetry.rewinds == b.telemetry.rewinds
            assert a.telemetry.active_kv == b.telemetry.active_kv
            assert a.telemetry.total_kv == b.telemetry.total_kv
            assert a.telemetry.offloaded_tokens == b.telemetry.offloaded_tokens

    def test_async_runs_are_reproducible(self, thaw_rewind_cfg):
        """Repeated async runs of one trace emit identical tokens.  The
        engine refills its host vectors and staging buffers while work
        dispatched from them is still queued; an upload that aliased the
        host buffer would make the tokens differ from run to run."""
        cfg, params = thaw_rewind_cfg

        def run():
            eng = PagedContinuousEngine(
                cfg, params, max_seq=256, n_lanes=2, max_active_pages=6,
                prefill_chunk=16, rewind_cooldown=12, async_pipeline=True,
                burst_prefill=False)
            return [r.result for r in _serve(eng, cfg, [(48, 70), (20, 50)])]

        first = run()
        for _ in range(2):
            for a, b in zip(first, run()):
                np.testing.assert_array_equal(a, b)

    def test_contiguous_with_offload(self, tiny_f32):
        """The contiguous engine shares the ring (incl. the folded-in
        offload freeze-mask fetch): async and sync must agree on tokens
        and offload telemetry, and offload must actually engage."""
        cfg, params = tiny_f32
        fc = dataclasses.replace(cfg.freeze, window=4, tau_mode="quantile",
                                 quantile=0.6, k_soft=1.0, page_size=8)
        cfg = dataclasses.replace(cfg, freeze=fc)
        lens = [(16, 40), (16, 24), (12, 30)]

        def run(async_pipeline):
            eng = ContinuousEngine(cfg, params, max_seq=96, n_lanes=2,
                                   async_pipeline=async_pipeline)
            return eng, _serve(eng, cfg, lens)

        se, sync_done = run(False)
        ae, async_done = run(True)
        assert se.offloader.n_offloads > 0, "offload never engaged"
        assert ae.offloader.n_offloads == se.offloader.n_offloads
        for a, b in zip(sync_done, async_done):
            np.testing.assert_array_equal(a.result, b.result)
            assert a.telemetry.offloaded_tokens == b.telemetry.offloaded_tokens


class TestTransferRegression:
    def test_contiguous_steps_never_block(self, tiny_f32):
        """With no offload there is no boundary maintenance at all: the
        async contiguous engine must complete a whole trace without a
        single blocking host transfer."""
        cfg, params = tiny_f32
        eng = ContinuousEngine(cfg, params, max_seq=96, n_lanes=2,
                               offload=False, async_pipeline=True)
        _serve(eng, cfg, [(16, 24), (12, 20), (10, 16)])
        assert eng.stats.steps > 0
        assert eng.stats.blocking_d2h == 0
        assert eng.stats.blocking_h2d == 0
        assert eng.stats.blocked_steps == 0
        assert eng.stats.async_d2h > 0          # the ring did the fetching

    def test_paged_blocks_only_at_boundary_ticks(self, tiny_f32):
        """The transfer-op counter regression: every blocking transfer of
        the async paged engine must belong to a page-boundary tick (the
        batched pool pull) or an admission install — plain decode steps
        issue zero blocking host transfers."""
        cfg, params = tiny_f32
        eng = PagedContinuousEngine(cfg, params, max_seq=160, n_lanes=2,
                                    max_active_pages=8,
                                    prefill_chunk=8, async_pipeline=True)
        _serve(eng, cfg, [(20, 40), (12, 24), (16, 30)])
        assert eng.stats.steps > 0
        assert eng.n_boundary_ticks > 0
        # one batched pull per boundary tick — and nothing else blocks D2H
        assert eng.stats.blocking_d2h == eng.n_boundary_ticks
        # one blocking H2D per push that had to carry K/V (admission
        # installs + dirty boundary pushes) — and nothing else
        assert eng.stats.blocking_h2d == eng.n_kv_pushes
        # a step may block only through boundary maintenance or an
        # install landing on it; plain decode steps never do
        assert eng.stats.blocked_steps <= eng.n_boundary_ticks \
            + eng.n_kv_pushes
        assert eng.stats.blocked_steps < eng.stats.steps

    def test_sync_mode_blocks_every_step(self, tiny_f32):
        """The depth-0 ring is the synchronous baseline: every decode step
        stalls on its fetch (host_blocked_fraction == 1)."""
        cfg, params = tiny_f32
        eng = PagedContinuousEngine(cfg, params, max_seq=96, n_lanes=1,
                                    max_active_pages=8, prefill_chunk=8,
                                    async_pipeline=False)
        _serve(eng, cfg, [(16, 16)])
        assert eng.stats.steps > 0
        assert eng.stats.host_blocked_fraction == 1.0


class TestSpeculativeThawStaging:
    def test_staged_thaw_is_remap_only(self, thaw_rewind_cfg):
        """On the thaw-heavy trace the async engine must serve at least
        one thaw from a staging slot: a metadata-only install (no K/V in
        the push) completed by a device-side copy."""
        cfg, params = thaw_rewind_cfg
        eng = PagedContinuousEngine(
            cfg, params, max_seq=256, n_lanes=2, max_active_pages=6,
            prefill_chunk=16, rewind_cooldown=12, async_pipeline=True,
            burst_prefill=False)
        _serve(eng, cfg, [(48, 70), (20, 50)])
        assert eng.ctl.n_thaw > 0
        assert eng.ctl.n_thaw_remap > 0, \
            "speculative staging never converted a thaw into a remap"
        assert not eng.ctl.pending_remaps      # all executed

    def test_controller_remap_semantics(self, tiny_f32):
        """Unit-level: a staged page installs into the SAME slot the
        upload path would pick, queues a device copy, refreshes the host
        pool copy, and leaves the K/V clean (metadata-only push)."""
        cfg, params = tiny_f32
        from repro.core.paging import PagedController
        L, P, S, page = 2, 4, 1, cfg.freeze.page_size
        kvh, hd = 2, cfg.head_dim
        ctl = PagedController(cfg=cfg, batch=1, max_active_pages=P)
        rng = np.random.RandomState(0)
        P_total = P + S
        pool = {"k": np.zeros((L, 1, P_total, page, kvh, hd), np.float32),
                "v": np.zeros((L, 1, P_total, page, kvh, hd), np.float32),
                "page_table": np.full((L, 1, P_total), -1, np.int32),
                "slot_mask": np.zeros((L, 1, P_total, page), bool)}
        fstate = {f: np.zeros((L, 1, P_total), np.int32)
                  for f in ("c", "d", "frozen_at")}
        fstate["frozen"] = np.zeros((L, 1, P_total), bool)
        kk = rng.randn(page, kvh, hd).astype(np.float32)
        for l in range(L):
            ctl.stash(l, 0, 5, kk, kk, d=50)
            ctl.stage_slots[(l, 0)] = [P]          # last slot reserved
            ctl.staged_keys[(l, 0, 5)] = P
        ctl.begin_tick()
        n = ctl.thaw_lane(pool, fstate, 0, 0, reserve_slots=0)
        assert n == L and ctl.n_thaw_remap == L and ctl.n_thaw_upload == 0
        assert not ctl.kv_dirty, "remap-only install must not dirty K/V"
        assert len(ctl.pending_remaps) == L
        for (l, lane, src, dst) in ctl.pending_remaps:
            assert lane == 0 and src == P and dst == 0, \
                "remap must target the slot the upload path would use"
            assert pool["page_table"][l, 0, dst] == 5
            np.testing.assert_array_equal(pool["k"][l, 0, dst], kk)
        assert not ctl.staged_keys                 # consumed

    def test_reserved_slots_freeze_equivalence(self):
        """The parity-critical math: a P+S pool whose S staging slots are
        unmapped, with reserved_slots=S, must make bit-identical freeze
        decisions to a plain P pool."""
        from repro.configs import get_config
        from repro.core.paging import PageFreezeState, page_freeze_update
        cfg = get_config("llama3-8b-tiny").freeze
        cfg = dataclasses.replace(cfg, page_size=8, window=8,
                                  tau_mode="fixed", tau=0.5, k_soft=0.7)
        B, P, S = 2, 5, 2
        rng = np.random.RandomState(1)
        pt = rng.randint(-1, 6, size=(B, P)).astype(np.int32)
        rel = rng.rand(B, P).astype(np.float32)

        def pad(a, fill):
            return np.concatenate(
                [a, np.full((B, S), fill, a.dtype)], axis=1)

        fz_p = PageFreezeState(
            c=jnp.asarray(rng.randint(0, 3, size=(B, P)), jnp.int32),
            d=jnp.zeros((B, P), jnp.int32),
            frozen=jnp.zeros((B, P), bool),
            frozen_at=jnp.zeros((B, P), jnp.int32))
        fz_t = PageFreezeState(
            c=jnp.asarray(pad(np.asarray(fz_p.c), 0)),
            d=jnp.asarray(pad(np.asarray(fz_p.d), 0)),
            frozen=jnp.asarray(pad(np.asarray(fz_p.frozen), False)),
            frozen_at=jnp.asarray(pad(np.asarray(fz_p.frozen_at), 0)))
        cur = jnp.asarray([5, 5], jnp.int32)
        step = jnp.asarray([9, 9], jnp.int32)
        new_p, info_p = page_freeze_update(
            fz_p, jnp.asarray(rel), jnp.asarray(pt), cur, step, cfg)
        new_t, info_t = page_freeze_update(
            fz_t, jnp.asarray(pad(rel, 0.0)), jnp.asarray(pad(pt, -1)),
            cur, step, cfg, reserved_slots=S)
        for a, b in zip(new_p, new_t):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b)[:, :P])
        np.testing.assert_array_equal(np.asarray(info_p["n_frozen"]),
                                      np.asarray(info_t["n_frozen"]))


class TestStagingSlotVisibility:
    def test_garbage_in_unmapped_staging_slot_is_invisible(self):
        """Kernel contract of the staging design: K/V written into a slot
        whose page-table entry is -1 (a staged, not-yet-remapped page)
        must not change attention output or page relevance — in the
        reference and in the Pallas kernel (interpret mode)."""
        from repro.kernels import ops as OPS
        rng = np.random.RandomState(0)
        B, P, page, H, KVH, hd = 2, 4, 8, 4, 2, 16
        q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
        k = rng.randn(B, P, page, KVH, hd).astype(np.float32)
        v = rng.randn(B, P, page, KVH, hd).astype(np.float32)
        sm = np.ones((B, P, page), bool)
        pt = np.tile(np.arange(P, dtype=np.int32), (B, 1))
        pt[:, -1] = -1                      # last slot = staging, unmapped
        sm[:, -1] = True                    # mask bits may even be set
        zeroed = k.copy(), v.copy()
        zeroed[0][:, -1] = 0
        zeroed[1][:, -1] = 0
        for force in (False, True):
            o_g, r_g = OPS.paged_decode_attention(
                q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(sm),
                jnp.asarray(pt), force_kernel=force)
            o_z, r_z = OPS.paged_decode_attention(
                q, jnp.asarray(zeroed[0]), jnp.asarray(zeroed[1]),
                jnp.asarray(sm), jnp.asarray(pt), force_kernel=force)
            np.testing.assert_array_equal(np.asarray(o_g), np.asarray(o_z))
            np.testing.assert_array_equal(np.asarray(r_g), np.asarray(r_z))


class TestDmaPrimitives:
    def test_ring_depth1_is_async_fifo(self):
        stats = TransferStats()
        ring = FetchRing(stats, depth=1)
        ring.push({"n": 1}, {"x": jnp.asarray([1, 2, 3])})
        ring.push({"n": 2}, {"x": jnp.asarray([4, 5, 6])})
        meta, host = ring.pop()
        assert meta["n"] == 1 and host["x"].tolist() == [1, 2, 3]
        assert stats.async_d2h == 1 and stats.blocking_d2h == 0
        meta, host = ring.pop()
        assert meta["n"] == 2
        assert ring.pop() is None

    def test_ring_depth0_counts_blocking(self):
        stats = TransferStats()
        stats.begin_step()
        ring = FetchRing(stats, depth=0)
        ring.push({}, {"x": jnp.zeros(4)})
        ring.pop()
        stats.end_step()
        assert stats.blocking_d2h == 1
        assert stats.blocked_steps == 1 and stats.steps == 1
        assert stats.host_blocked_fraction == 1.0

    def test_staging_buffers_are_reused(self):
        st = HostStaging()
        a = st.buf("x", (2, 3), np.float32)
        a[:] = 1
        b = st.buf("x", (2, 3), np.float32)
        assert a is b                       # same allocation, kept contents
        assert b.sum() == 6
        c = st.buf("x", (4, 3), np.float32)  # shape change -> realloc
        assert c is not b
