"""PagedContinuousEngine: token-stream parity with the contiguous engine,
chunked-prefill interleaving (no head-of-line blocking), bounded-pool decode
with host swapping, and per-lane reset guarantees."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD
from repro.serving.engine import (ContinuousEngine, PagedContinuousEngine,
                                  Request)
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def tiny_f32():
    """f32 tiny model (exact argmax parity across summation orders) with a
    small page size so pools stay cheap."""
    cfg = get_config("llama3-8b-tiny")
    fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                             recovery_enabled=False)
    cfg = dataclasses.replace(cfg, freeze=fc, dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestParity:
    """With freezing disabled and a pool large enough for the whole trace,
    paged and contiguous continuous batching are the same math — token
    streams must be identical."""

    def test_identical_token_streams(self, tiny_f32):
        cfg, params = tiny_f32
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, size=n)
                   for n in (16, 10, 16, 7)]
        n_toks = [12, 8, 10, 9]

        def run(paged):
            if paged:
                eng = PagedContinuousEngine(
                    cfg, params, max_seq=96, n_lanes=2, max_active_pages=8,
                    enable_freeze=False, prefill_chunk=8)
            else:
                eng = ContinuousEngine(cfg, params, max_seq=96, n_lanes=2,
                                       enable_freeze=False, offload=False)
            s = Scheduler(eng)
            uids = [s.submit(p, n, SamplingParams.greedy())
                    for p, n in zip(prompts, n_toks)]
            s.run()
            return [s.done[u].result for u in uids]

        for i, (a, b) in enumerate(zip(run(False), run(True))):
            np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


class TestChunkedPrefill:
    def test_resident_lane_decodes_during_long_admission(self, tiny_f32):
        """A long prompt admitted while another lane is decoding must be
        prefilled in fine-grained chunks, with the resident lane producing
        decode steps between admit_start and admit-complete."""
        cfg, params = tiny_f32
        rng = np.random.RandomState(1)
        from repro.serving.engine import Request
        eng = PagedContinuousEngine(cfg, params, max_seq=160, n_lanes=2,
                                    max_active_pages=12, enable_freeze=False,
                                    prefill_chunk=8)
        short = Request(1, rng.randint(0, cfg.vocab_size, size=8).astype(
            np.int32), 40, SamplingParams.greedy())
        long = Request(2, rng.randint(0, cfg.vocab_size, size=60).astype(
            np.int32), 8, SamplingParams.greedy())
        eng.admit(short)
        while eng.prefills:          # install the short request...
            eng.step_once()
        eng.step_once()              # ...and start decoding it
        eng.admit(long)              # now the engine is busy: chunked path
        finished = []
        while len(finished) < 2:
            finished += eng.step_once()
        assert {r.uid for r in finished} == {1, 2}
        assert short.result.shape == (40,)
        assert long.result.shape == (8,)
        ev = {(e["event"], e["uid"]): e["wall_step"] for e in eng.events}
        start, done = ev[("admit_start", 2)], ev[("admit", 2)]
        # 60-token prompt -> 64 bucket -> 8 chunks of 8, one per decode
        # step: the resident lane advanced throughout the admission
        chunks = [e for e in eng.events if e["event"] == "prefill_chunk"
                  and e["uid"] == 2]
        assert len(chunks) == 8
        assert done - start >= 8, "admission did not interleave with decode"

    def test_idle_engine_bursts_admission(self, tiny_f32):
        """With no resident decode work, chunking buys nothing: the burst
        schedule grows chunks to powers of two and admits in few steps."""
        cfg, params = tiny_f32
        rng = np.random.RandomState(3)
        from repro.serving.engine import Request
        eng = PagedContinuousEngine(cfg, params, max_seq=160, n_lanes=2,
                                    max_active_pages=12, enable_freeze=False,
                                    prefill_chunk=8)
        req = Request(1, rng.randint(0, cfg.vocab_size, size=60).astype(
            np.int32), 8, SamplingParams.greedy())
        eng.admit(req)
        eng.step_once()
        chunks = [e for e in eng.events if e["event"] == "prefill_chunk"]
        assert len(chunks) == 1 and chunks[0]["done"] == 64

    def test_overflow_prompt_pages_survive_install(self):
        """A prompt whose pages exceed the device pool must keep its oldest
        pages in the host store after install (regression: write_lane's
        internal drop_lane used to delete the just-stashed overflow), and
        they must swap back in during decode so early context is never
        permanently lost."""
        cfg = get_config("llama3-8b-tiny")
        fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                                 tau_mode="quantile", quantile=0.6,
                                 k_soft=1.0, recovery_enabled=False)
        cfg = dataclasses.replace(cfg, freeze=fc)
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(4)
        from repro.serving.engine import Request
        eng = PagedContinuousEngine(cfg, params, max_seq=256, n_lanes=1,
                                    max_active_pages=6, prefill_chunk=16)
        # 48-token prompt -> 64 bucket = 8 pages > 5 resident: 3 overflow
        req = Request(1, rng.randint(0, cfg.vocab_size, size=48).astype(
            np.int32), 40, SamplingParams(temperature=0.7))
        eng.admit(req)
        while eng.prefills:
            eng.step_once()
        overflow = {k[2] for k in eng.ctl.store if k[1] == 0}
        assert overflow == {0, 1, 2}, \
            f"overflow prompt pages lost at install: {overflow}"
        swaps_before = eng.ctl.n_swap_in
        while eng.lanes[0].request is not None:
            eng.step_once()
        assert eng.ctl.n_swap_in > swaps_before, \
            "overflow pages never swapped back in during decode"

    def test_no_decode_lane_still_progresses(self, tiny_f32):
        """An admission into an otherwise-empty engine must complete even
        though no decode steps run."""
        cfg, params = tiny_f32
        rng = np.random.RandomState(2)
        eng = PagedContinuousEngine(cfg, params, max_seq=96, n_lanes=1,
                                    max_active_pages=8, enable_freeze=False,
                                    prefill_chunk=8)
        s = Scheduler(eng)
        uid = s.submit(rng.randint(0, cfg.vocab_size, size=30), 6,
                       SamplingParams.greedy())
        s.run()
        assert s.done[uid].result.shape == (6,)


class TestRecovery:
    """Entropy-guided recovery on the paged path: parity with the
    contiguous oracle, page-granular rewinds, and host thaw servicing."""

    def test_recovery_token_parity_with_contiguous_oracle(self, tiny_f32):
        """With freezing never firing (fixed tau = 0) but sustained entropy
        spikes, both engines run the identical recovery ladder — including
        RR rewinds, which on the paged path exercise the device-side slot
        invalidation and replay.  Token streams must be identical to the
        contiguous engine (the oracle), and rewinds must actually happen
        or the test is vacuous."""
        cfg, params = tiny_f32
        fc = dataclasses.replace(cfg.freeze, tau_mode="fixed", tau=0.0,
                                 recovery_enabled=True,
                                 entropy_abs_threshold=0.5, rewalk_tokens=4)
        cfg = dataclasses.replace(cfg, freeze=fc)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, size=n)
                   for n in (16, 10, 16, 7)]
        n_toks = [14, 10, 12, 9]

        def run(paged):
            if paged:
                eng = PagedContinuousEngine(
                    cfg, params, max_seq=96, n_lanes=2, max_active_pages=10,
                    prefill_chunk=8, rewind_cooldown=8)
            else:
                eng = ContinuousEngine(cfg, params, max_seq=96, n_lanes=2,
                                       offload=False, rewind_cooldown=8)
            s = Scheduler(eng)
            uids = [s.submit(p, n, SamplingParams.greedy())
                    for p, n in zip(prompts, n_toks)]
            s.run()
            rewinds = sum(s.done[u].telemetry.rewinds for u in uids)
            return [s.done[u].result for u in uids], rewinds

        (a, rw_c), (b, rw_p) = run(False), run(True)
        assert rw_c > 0, "no rewinds fired — parity test is vacuous"
        assert rw_p == rw_c
        for i, (x, y) in enumerate(zip(a, b)):
            np.testing.assert_array_equal(x, y, err_msg=f"request {i}")

    @pytest.mark.parametrize("async_pipeline", [False, True])
    def test_rewind_landing_on_page_boundary(self, tiny_f32, async_pipeline):
        """A rewind whose target position is exactly a page boundary must
        unmap the (now wholly invalid) tail page and leave its
        re-allocation to the next page-boundary tick; greedy replay then
        reproduces the never-rewound stream.  Runs in BOTH pipeline
        modes: `_rewind_lane` drains the async result ring at entry, so
        an injected rewind sees current host bookkeeping instead of
        surgery computed against a position one deferred commit stale
        (the per-iteration flush below only keeps the drive loop's pos
        reads exact — the drain is what makes the rewind itself safe)."""
        cfg, params = tiny_f32
        fc = dataclasses.replace(cfg.freeze, recovery_enabled=True,
                                 entropy_abs_threshold=1e9,  # no organic RR
                                 rewalk_tokens=8)
        cfg = dataclasses.replace(cfg, freeze=fc)
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, cfg.vocab_size, size=14).astype(np.int32)

        def run(rewind):
            eng = PagedContinuousEngine(cfg, params, max_seq=96, n_lanes=1,
                                        max_active_pages=10, prefill_chunk=8,
                                        async_pipeline=async_pipeline)
            req = Request(1, prompt, 30, SamplingParams.greedy())
            eng.admit(req)
            while eng.prefills:
                eng.step_once()
            # bucket 16 -> pos starts 16; 16 commits -> pos 32 (flush per
            # iteration so the async ring's deferred commit can't make the
            # loop overshoot the boundary-landing target)
            while int(eng.pos[0]) < 32:
                eng.step_once()
                eng.flush()
            if rewind:
                assert eng._rewind_lane(0)
                assert int(eng.pos[0]) == 24 and 24 % eng.page == 0
                pt = np.asarray(eng.state.page_table[:, 0])
                assert (pt[pt >= 0] < 24 // eng.page).all(), \
                    "wholly-rewound pages must be unmapped"
            while eng.lanes[0].request is not None:
                eng.step_once()
                eng.flush()
            return req.result

        base, rew = run(False), run(True)
        np.testing.assert_array_equal(base, rew)

    def test_thaw_with_full_pool_evicts_coldest(self, tiny_f32):
        """thaw_lane on a saturated pool must evict the coldest resident
        page (frozen pages first), stash it with the forced-freeze timer,
        and install the thawed page in its slot."""
        cfg, params = tiny_f32
        from repro.core.paging import PagedController
        L, P, page = 2, 4, cfg.freeze.page_size
        kvh, hd = 2, cfg.head_dim
        ctl = PagedController(cfg=cfg, batch=1, max_active_pages=P)
        rng = np.random.RandomState(0)
        pool = {"k": rng.randn(L, 1, P, page, kvh, hd).astype(np.float32),
                "v": rng.randn(L, 1, P, page, kvh, hd).astype(np.float32),
                "page_table": np.tile(np.arange(5, 9, dtype=np.int32),
                                      (L, 1, 1)),
                "slot_mask": np.ones((L, 1, P, page), bool)}
        fstate = {"c": np.tile(np.array([3, 0, 1, 0], np.int32), (L, 1, 1)),
                  "d": np.zeros((L, 1, P), np.int32),
                  "frozen": np.tile(np.array([True, False, False, False]),
                                    (L, 1, 1)),
                  "frozen_at": np.zeros((L, 1, P), np.int32)}
        stash_k = rng.randn(page, kvh, hd).astype(np.float32)
        for l in range(L):
            ctl.stash(l, 0, 2, stash_k, stash_k, d=50)
        n = ctl.thaw_lane(pool, fstate, 0, 0, keep_gids=(8,),
                          reserve_slots=0)
        assert n == L and ctl.n_thaw == L
        for l in range(L):
            # gid 2 resident and un-frozen, in the evicted page's slot
            where = np.nonzero(pool["page_table"][l, 0] == 2)[0]
            assert len(where) == 1 and where[0] == 0, \
                "thaw must land in the frozen victim's slot"
            assert not fstate["frozen"][l, 0, where[0]]
            np.testing.assert_array_equal(pool["k"][l, 0, where[0]], stash_k)
            # the frozen victim (gid 5) was stashed in turn, durable timer
            key = (l, 0, 5)
            assert key in ctl.store and key in ctl.frozen_meta
            assert ctl.frozen_meta[key]["d"] == cfg.freeze.page_size
            assert 5 not in pool["page_table"][l, 0]

    def test_thaw_of_chunked_prefill_overflow_page(self):
        """A page stashed at install because the prompt overflowed the
        device pool must be recoverable by an entropy-driven thaw: with
        the pool still saturated, thaw_lane evicts a cold resident page
        and remaps the overflow page into its slot."""
        cfg = get_config("llama3-8b-tiny")
        fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                                 tau_mode="quantile", quantile=0.6,
                                 k_soft=1.0, recovery_enabled=False)
        cfg = dataclasses.replace(cfg, freeze=fc)
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(4)
        eng = PagedContinuousEngine(cfg, params, max_seq=256, n_lanes=1,
                                    max_active_pages=6, prefill_chunk=16)
        # 48-token prompt -> 64 bucket = 8 pages > 5 resident: gids 0..2
        # overflow into the host store at install
        req = Request(1, rng.randint(0, cfg.vocab_size, size=48).astype(
            np.int32), 40, SamplingParams(temperature=0.7))
        eng.admit(req)
        while eng.prefills:
            eng.step_once()
        assert {k[2] for k in eng.ctl.frozen_meta if k[1] == 0} \
            >= {0, 1, 2}
        pool, fstate = eng._pull_lanes([0])
        n = eng.ctl.thaw_lane(pool, fstate, 0, 0,
                              keep_gids=eng._keep_gids(0), reserve_slots=1)
        assert n > 0 and eng.ctl.n_thaw == n
        thawed = [gid for gid in (0, 1, 2)
                  if all((pool["page_table"][l, 0] == gid).any()
                         for l in range(eng.L_attn))]
        assert thawed, "no overflow prompt page came back resident"
        eng._push_lanes(pool, fstate, [0])
        # decode still completes after the host rearranged the pool
        while eng.lanes[0].request is not None:
            eng.step_once()
        assert req.result.shape == (40,)

    def test_entropy_spikes_drive_thaws_end_to_end(self):
        """Full loop: freeze pressure stashes pages, sustained entropy
        spikes escalate to FR, pending thaws are serviced at page-boundary
        ticks, and every request still completes with no host-store
        leaks."""
        cfg = get_config("llama3-8b-tiny")
        fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                                 tau_mode="quantile", quantile=0.6,
                                 k_soft=0.7, recovery_enabled=True,
                                 entropy_abs_threshold=0.5, rewalk_tokens=6)
        cfg = dataclasses.replace(cfg, freeze=fc, dtype="float32")
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        eng = PagedContinuousEngine(cfg, params, max_seq=256, n_lanes=2,
                                    max_active_pages=6, prefill_chunk=16,
                                    rewind_cooldown=12)
        s = Scheduler(eng)
        uids = [s.submit(rng.randint(0, cfg.vocab_size, size=sp), n,
                         SamplingParams(temperature=0.7))
                for sp, n in ((48, 70), (20, 50))]
        s.run()
        for u, n in zip(uids, (70, 50)):
            assert s.done[u].result.shape == (n,)
        assert eng.ctl.n_thaw > 0, "no thaw was ever serviced"
        assert sum(s.done[u].telemetry.rewinds for u in uids) > 0
        assert any(s.done[u].telemetry.recovery_events for u in uids)
        assert not eng.ctl.frozen_meta and not eng.ctl.store


class TestBoundedPool:
    @pytest.fixture(scope="class")
    def bounded_run(self):
        cfg = get_config("llama3-8b-tiny")
        fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                                 tau_mode="quantile", quantile=0.6,
                                 k_soft=1.0, recovery_enabled=False)
        cfg = dataclasses.replace(cfg, freeze=fc)
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        eng = PagedContinuousEngine(cfg, params, max_seq=256, n_lanes=2,
                                    max_active_pages=6, prefill_chunk=16)
        s = Scheduler(eng)
        uids = [s.submit(rng.randint(0, cfg.vocab_size, size=sp), n,
                         SamplingParams(temperature=0.7))
                for sp, n in ((48, 60), (12, 20), (20, 24))]
        s.run()
        return eng, s, uids

    def test_all_complete_and_swapping_happened(self, bounded_run):
        eng, s, uids = bounded_run
        for u, n in zip(uids, (60, 20, 24)):
            assert s.done[u].result.shape == (n,)
        # context (64 prompt bucket + 60 decode) far exceeds the 48-slot
        # pool: pages must have been swapped out and back in
        assert eng.ctl.n_swap_out > 0
        assert eng.ctl.n_swap_in > 0

    def test_active_kv_is_bounded_by_pool(self, bounded_run):
        """The whole point: per-lane active KV never exceeds P * page even
        though the context grows past it."""
        eng, s, uids = bounded_run
        t = s.done[uids[0]].telemetry
        pool_slots = 6 * 8
        assert max(t.active_kv) <= pool_slots
        assert t.total_kv[-1] > pool_slots       # context outgrew the pool
        assert t.compression > 0.3

    def test_lane_reuse_leaks_nothing(self, bounded_run):
        """After the run every lane retired: page tables must be unmapped
        and the controller's per-lane store empty."""
        eng, _, _ = bounded_run
        assert int(np.asarray((eng.state.page_table >= 0).sum())) == 0
        assert not eng.ctl.frozen_meta
        assert eng.kv_device_bytes == eng.state.k.nbytes + eng.state.v.nbytes


class TestLaneTransfer:
    """The boundary tick's pull and push move exactly the lanes asked
    for: the other lanes' columns never leave the device, and one program
    per direction serves any number of lanes."""

    @pytest.fixture(scope="class")
    def tiny_freeze(self):
        cfg = get_config("llama3-8b-tiny")
        fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                                 tau_mode="quantile", quantile=0.6,
                                 k_soft=1.0, recovery_enabled=False)
        cfg = dataclasses.replace(cfg, freeze=fc, dtype="float32")
        return cfg, MD.init_params(jax.random.PRNGKey(0), cfg)

    @staticmethod
    def _engine(tiny_freeze):
        from repro.serving.config import ServingConfig
        cfg, params = tiny_freeze
        return PagedContinuousEngine(cfg, params, serving=ServingConfig(
            max_seq=128, n_lanes=4, max_active_pages=5, prefill_chunk=16))

    def _busy(self, tiny_freeze):
        """A 4-lane engine with every lane decoding and pages frozen, its
        fetch ring drained."""
        eng = self._engine(tiny_freeze)
        s = Scheduler(eng)
        rng = np.random.RandomState(3)
        for sp in (32, 16, 24, 8):
            s.submit(rng.randint(0, eng.cfg.vocab_size, size=sp), 60,
                     SamplingParams.greedy())
        for _ in range(30):
            s.step()
        eng.flush()
        assert all(l.request is not None for l in eng.lanes)
        assert eng.ctl.n_swap_out > 0
        return eng

    @staticmethod
    def _leaves(eng):
        return [np.array(a) for a in jax.tree_util.tree_leaves(eng.state)]

    def test_round_trip_leaves_the_state_bit_identical(self, tiny_freeze):
        eng = self._busy(tiny_freeze)
        before = self._leaves(eng)
        pool, fstate = eng._pull_lanes([1, 3])
        eng._push_lanes(pool, fstate, [1, 3], kv=True)
        eng._push_lanes(pool, fstate, [1, 3], kv=False)
        after = self._leaves(eng)
        assert len(before) == len(after)
        for a, b in zip(before, after):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()

    def test_push_leaves_other_lanes_bit_identical(self, tiny_freeze):
        eng = self._busy(tiny_freeze)
        names = eng._POOL_FIELDS + eng._FZ_FIELDS
        before = [np.array(a) for a in eng._state_arrs()]
        pool, fstate = eng._pull_lanes([1, 3])
        new = dict(pool, **fstate)
        rng = np.random.RandomState(0)
        for f in names:
            a = new[f]
            if a.dtype == bool:
                new[f] = ~a
            elif np.issubdtype(a.dtype, np.integer):
                new[f] = a + 1
            else:
                new[f] = rng.standard_normal(a.shape).astype(a.dtype)
        eng._push_lanes({f: new[f] for f in eng._POOL_FIELDS},
                        {f: new[f] for f in eng._FZ_FIELDS}, [1, 3])
        after = [np.array(a) for a in eng._state_arrs()]
        for f, b, a in zip(names, before, after):
            for lane in (0, 2):
                assert a[:, lane].tobytes() == b[:, lane].tobytes(), f
            for j, lane in enumerate((1, 3)):
                assert a[:, lane].tobytes() == new[f][:, j].tobytes(), f
                assert a[:, lane].tobytes() != b[:, lane].tobytes(), f

    # greedy tokens of the run below, recorded when every pull and push
    # still moved all n_lanes columns
    RECORDED = [
        [470, 187, 396, 8, 483, 47, 101, 302, 227, 336, 3, 355, 196, 84,
         472, 302, 299, 215, 268, 128, 468],
        [82, 337, 277, 394, 70, 83, 450, 355, 432, 478, 469, 215, 13, 244,
         199, 469, 172, 124, 275, 493, 82, 50, 168, 105, 83, 471, 234, 195,
         232, 60, 355, 187, 436, 450, 197, 193],
        [474, 282, 242, 390, 249, 409, 493, 172, 25, 36, 154, 23, 403, 282,
         108, 156, 372, 251, 157, 348, 460],
        [124, 324, 87, 484, 114, 501, 290, 247, 386, 482, 124, 60, 211, 65,
         312, 227, 342, 144, 207, 429, 380, 135, 135, 376, 401, 302, 450,
         410, 450, 94],
        [153, 184, 252, 104, 449, 226, 274, 153, 184, 209, 244, 382, 384, 32,
         179, 161, 397, 48, 230, 352, 267, 483, 191, 260, 226],
        [199, 475, 295, 342, 248, 165, 323, 310, 150, 318, 111, 206, 153,
         258, 286, 414, 324, 435, 303],
    ]

    def test_greedy_tokens_match_the_recorded_run(self, tiny_freeze):
        """Six greedy requests over four lanes: ticks of 1, 2 and 4 lanes
        with pages swapped out and back in."""
        eng = self._engine(tiny_freeze)
        s = Scheduler(eng)
        rng = np.random.RandomState(7)
        uids = [s.submit(rng.randint(0, eng.cfg.vocab_size, size=sp), n,
                         SamplingParams.greedy())
                for sp, n in ((32, 21), (16, 36), (8, 21), (24, 30),
                              (16, 25), (8, 19))]
        ticks = []
        tick = eng._boundary_tick

        def spy(boundary):
            ticks.append(len(boundary))
            return tick(boundary)
        eng._boundary_tick = spy
        s.run()
        assert set(ticks) == {1, 2, 4}
        # the same swap schedule as the recorded run
        assert (eng.ctl.n_swap_out, eng.ctl.n_swap_in) == (48, 27)
        assert [s.done[u].result.tolist() for u in uids] == self.RECORDED

    def test_one_program_per_direction_whatever_the_lanes(self, tiny_freeze):
        eng = self._engine(tiny_freeze)
        assert eng._gather_lane._cache_size() == 0
        assert eng._scatter_lane._cache_size() == 0
        for lanes in ([2], [0, 3], [0, 1, 2, 3]):
            pool, fstate = eng._pull_lanes(lanes)
            eng._push_lanes(pool, fstate, lanes, kv=True)
        assert eng._gather_lane._cache_size() == 1
        assert eng._scatter_lane._cache_size() == 1
        # the metadata-only push is the one other field set
        for lanes in ([2], [0, 3], [0, 1, 2, 3]):
            pool, fstate = eng._pull_lanes(lanes)
            eng._push_lanes(pool, fstate, lanes, kv=False)
        assert eng._gather_lane._cache_size() == 1
        assert eng._scatter_lane._cache_size() == 2
