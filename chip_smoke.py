"""Smoke check: the paged serving path, end to end, on one TPU chip.

The model is llama3-8b at its published widths (d_model 4096, 32 query /
8 KV heads, head_dim 128, d_ff 14336, vocab 128256, bf16) with random
weights from a seed.  Depth is cut from 32 layers to 16, read as stage one
of a two-stage pipeline: the 32-layer model's bf16 weights alone (16.1 GB)
do not fit in a v5e's 16 GB.  That cut is the only reduction.

Phases, all in this one process (it holds the chip; it starts no other):

  device  fail unless JAX's first device is a TPU
  build   launch/serve.py's construction path: ServingConfig ->
          PagedContinuousEngine -> Scheduler, with freeze, recovery and the
          async pipeline on (serve.py's defaults)
  serve   a seeded trace of 24 requests (prompts of 512-3072 tokens, 64 new
          tokens each, greedy and temperature 0.7) over 16 lanes of 32
          active pages: long prompts overflow the pool, so pages stash to
          the host store; swap-ins and thaws are counted, not required
          (random weights raise no entropy spikes to thaw on)
  kernel  the compiled decode step holds the Pallas kernel
          (tpu_custom_call), and the kernel matches kernels/ref.py at one
          layer's pool shape
  server  ServingServer over AsyncServingEngine on an ephemeral port:
          3 requests over HTTP, streamed tokens equal each result

Any failure raises and exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Compiled programs go to JAX's persistent cache (launch/serve.py
``enable_compile_cache``), so a second run compiles less.

    python chip_smoke.py
"""
from __future__ import annotations

import asyncio
import functools
import json
import pathlib
import sys
import time
from typing import Dict, List, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.runtime import trace_guard  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.paged_decode_attn import \
    paged_decode_attention_kernel  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serving.config import ServingConfig  # noqa: E402
from repro.serving.engine import RequestStatus  # noqa: E402
from repro.serving.sampling import SamplingParams  # noqa: E402
from repro.serving.scheduler import Scheduler  # noqa: E402
from repro.serving.server import AsyncServingEngine, ServingServer  # noqa: E402

ARCH = "llama3-8b"
NUM_LAYERS = 16                  # of 32: stage one of a two-stage pipeline
SEED = 0
N_LANES = 16
PAGES = 32                       # active pages per lane; page = 64 tokens
MAX_SEQ = 4096
# prompts are drawn from these lengths: the engine pads a prompt to a
# power-of-two bucket (3072 has none below MAX_SEQ and stays exact), so
# the set of prefill shapes, and of compiles, stays closed
PROMPT_LENS = (512, 1024, 2048, 3072)
N_REQUESTS = 24
N_NEW = 64
PREFILL_CHUNK = 512
N_HTTP = 3
# Kernel vs reference, bf16 pool: the kernel's output is rounded to bf16
# (2**-9 relative) and its P.V product runs on the MXU with bf16 operands
# (2**-8 relative on the probabilities); the same bound as the bf16 sweep
# in tests/test_kernels.py.  A wrong page, mask or scale errs by O(1).
OUT_TOL = dict(rtol=2e-2, atol=2e-2)
# The relevance is mean |Q.K| over exact bf16 products summed in f32, so
# it differs from the float32 reference only by summation order.
REL_TOL = dict(rtol=1e-3, atol=1e-3)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device() -> Dict[str, object]:
    """The device JAX reports; exits unless it is a TPU."""
    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is on platform {d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileClock:
    """Seconds JAX spent compiling (or fetching from the persistent cache)
    since construction, from its own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.n += 1


def build(cfg, params, *, n_lanes: int, pages: int, max_seq: int,
          prefill_chunk: int) -> Tuple[object, Scheduler]:
    """The engine and scheduler, through launch/serve.py's construction."""
    sv = ServingConfig(max_seq=max_seq, n_lanes=n_lanes,
                       max_active_pages=pages, prefill_chunk=prefill_chunk)
    eng = serve.build_engine(cfg, params, sv)
    return eng, Scheduler(eng)


def make_trace(vocab: int, n: int, prompt_lens, n_new: int, seed: int
               ) -> List[Tuple[np.ndarray, int, SamplingParams]]:
    """``n`` seeded requests cycling through ``prompt_lens``; odd ones
    sample at temperature 0.7, even ones are greedy."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        prompt = rng.randint(0, vocab, size=prompt_lens[i % len(prompt_lens)])
        sp = SamplingParams(temperature=0.7) if i % 2 \
            else SamplingParams.greedy()
        out.append((prompt.astype(np.int32), n_new, sp))
    return out


def warm_up(eng, sched: Scheduler, prompt_lens, n_new: int) -> None:
    """Compile every prefill-chunk shape the trace can hit, then serve one
    greedy and one sampled request for the decode step, the boundary tick
    and both samplers."""
    for n in sorted(set(prompt_lens)):
        eng.warm_prefill(n, n_new)
    for prompt, _, sp in make_trace(eng.cfg.vocab_size, 2, prompt_lens[:1],
                                    n_new, seed=SEED + 1):
        sched.submit(prompt, 8, sp)
    sched.run()
    sched.done.clear()


def serve_trace(eng, sched: Scheduler, trace) -> Dict[str, object]:
    """Serve ``trace`` to the end and check every result; returns the
    counts the smoke prints."""
    ctl = eng.ctl
    before = (eng.wall_step, ctl.n_swap_out, ctl.n_swap_in, ctl.n_thaw)
    t0 = time.perf_counter()
    with trace_guard(eng, label="trace") as tg:
        uids = [sched.submit(p, n, sp) for p, n, sp in trace]
        sched.run()
    seconds = time.perf_counter() - t0
    vocab = eng.cfg.vocab_size
    tokens = 0
    for uid, (_, n, _) in zip(uids, trace):
        r = sched.done[uid]
        assert r.status == RequestStatus.COMPLETED, (uid, r.status)
        assert len(r.result) == n, (uid, len(r.result), n)
        res = np.asarray(r.result)
        assert ((res >= 0) & (res < vocab)).all(), (uid, res)
        ent = np.asarray(r.telemetry.entropy, np.float64)
        assert ent.size and np.isfinite(ent).all(), (uid, ent)
        tokens += len(r.result)
    after = (eng.wall_step, ctl.n_swap_out, ctl.n_swap_in, ctl.n_thaw)
    counts = dict(zip(("engine_steps", "swap_out", "swap_in", "thaws"),
                      (a - b for a, b in zip(after, before))))
    return {"requests": len(uids), "tokens": tokens, **counts,
            "retraces_after_warmup": tg.n_retraces,
            "retraced": tg.growth, "trace_seconds": seconds}


def decode_step_hlo(eng) -> str:
    """The engine's compiled decode step, as text (same arguments as
    ``step_once`` passes, so the compile cache serves it)."""
    up = lambda x: jnp.asarray(np.array(x))  # noqa: E731
    return eng._step.lower(
        eng.params, token=up(eng.tok), pos=up(eng.pos), step=up(eng.step),
        tail_slot=up(eng.tail_slot), state=eng.state,
        live=up(np.ones(eng.n_lanes, bool))).compile().as_text()


def kernel_vs_reference(B: int, P: int, page: int, KVH: int, hd: int, H: int,
                        *, seed: int = SEED, interpret: bool = False
                        ) -> Dict[str, float]:
    """The paged kernel against ``kernels/ref.py`` on one layer's pool: a
    bf16 pool mixing unmapped, invisible, partly masked and int8-quantized
    pages.  The reference runs in float32 at full matmul precision.
    Returns the largest errors; raises if they exceed the tolerances."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, hd), dt)
    k = jax.random.normal(ks[1], (B, P, page, KVH, hd), dt)
    v = jax.random.normal(ks[2], (B, P, page, KVH, hd), dt)
    slot_mask = jax.random.uniform(ks[3], (B, P, page)) < 0.8
    page_table = jnp.where(jax.random.uniform(ks[4], (B, P)) < 0.85,
                           jnp.arange(P)[None, :], -1).astype(jnp.int32)
    page_visible = jax.random.uniform(ks[5], (B, P)) < 0.9
    page_quant = (jax.random.uniform(ks[6], (B, P)) < 0.3).astype(jnp.int32)
    # quantized pages hold integer payloads in the pool dtype (core/quant.py)
    ints = lambda x: jnp.clip(jnp.round(x.astype(jnp.float32) * 40),  # noqa: E731
                              -127, 127).astype(dt)
    qmask = page_quant.astype(bool)[:, :, None, None, None]
    k = jnp.where(qmask, ints(k), k)
    v = jnp.where(qmask, ints(v), v)
    kv_scales = jax.random.uniform(ks[7], (B, P, 2, KVH), jnp.float32,
                                   1e-3, 3e-2)
    args = (q, k, v, slot_mask, page_table, page_visible, page_quant,
            kv_scales)
    kern = jax.jit(functools.partial(paged_decode_attention_kernel,
                                     interpret=interpret))
    out_k, rel_k = jax.device_get(kern(*args))
    with jax.default_matmul_precision("highest"):
        out_r, rel_r = jax.device_get(
            jax.jit(ref.paged_decode_attention_ref)(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), *args[3:]))
    out_k = np.asarray(out_k, np.float32)
    np.testing.assert_allclose(out_k, out_r, **OUT_TOL)
    np.testing.assert_allclose(rel_k, rel_r, **REL_TOL)
    return {"out_max_abs_err": float(np.abs(out_k - out_r).max()),
            "rel_max_abs_err": float(np.abs(rel_k - rel_r).max())}


async def _http_generate(port: int, prompt: np.ndarray, n_tokens: int,
                         greedy: bool) -> Dict[str, object]:
    """One POST /v1/generate over a plain socket; replays the SSE stream
    into the committed tokens and returns the terminal event with them."""
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "n_tokens": n_tokens, "greedy": greedy}).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    raw = (await reader.read()).decode()
    writer.close()
    head, _, stream = raw.partition("\r\n\r\n")
    assert head.startswith("HTTP/1.1 200"), head
    toks: List[int] = []
    for frame in stream.strip().split("\n\n"):
        lines = dict(line.split(": ", 1) for line in frame.split("\n"))
        data = json.loads(lines["data"])
        if lines["event"] == "token":
            assert data["index"] == len(toks), (data, len(toks))
            toks.append(data["token"])
        elif lines["event"] == "rewind":
            del toks[data["to"]:]
        else:
            data["streamed"] = toks
            return data
    raise AssertionError("stream ended without a terminal event")


def serve_http(sched: Scheduler, requests, timeout_s: float = 600.0
               ) -> Dict[str, object]:
    """Serve ``requests`` through ServingServer over HTTP on an ephemeral
    port; every streamed sequence must equal the request's result, and the
    serve loop must have swallowed no exception."""

    async def go():
        aeng = AsyncServingEngine(sched)
        srv = ServingServer(aeng, port=0)
        await srv.start()
        try:
            calls = asyncio.gather(*(
                _http_generate(srv.port, p, n, sp.temperature <= 0)
                for p, n, sp in requests))
            deadline = time.monotonic() + timeout_s
            while not calls.done():
                # fail fast: the serve loop counts a failing step and goes
                # on looping, which a client would only see as a hang
                if aeng.last_exception is not None:
                    calls.cancel()
                    raise aeng.last_exception
                assert time.monotonic() < deadline, "HTTP requests timed out"
                await asyncio.sleep(0.05)
            return await calls, aeng.unhandled_exceptions
        finally:
            await srv.close()

    done, unhandled = asyncio.run(go())
    assert unhandled == 0, unhandled
    for (prompt, n, _), ev in zip(requests, done):
        assert ev["status"] == RequestStatus.COMPLETED.value, ev["status"]
        assert ev["streamed"] == ev["tokens"], ev
        assert len(ev["tokens"]) == n, ev
        match = [r for r in sched.done.values()
                 if np.array_equal(r.prompt, prompt)]
        assert len(match) == 1 and \
            [int(t) for t in match[0].result] == ev["tokens"], ev
    return {"http_requests": len(done), "unhandled_exceptions": unhandled}


def main() -> None:
    device = check_device()
    log(f"device: {device}")
    cache_dir = serve.enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    clock = CompileClock()

    cfg = serve.model_config(ARCH, num_layers=NUM_LAYERS)
    log(f"model: {cfg.name} at published widths (d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}); reduced: num_layers 32 -> {cfg.num_layers} "
        f"(stage one of a two-stage pipeline)")
    t0 = time.perf_counter()
    params = serve.init_model(cfg, SEED)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    eng, sched = build(cfg, params, n_lanes=N_LANES, pages=PAGES,
                       max_seq=MAX_SEQ, prefill_chunk=PREFILL_CHUNK)
    jax.block_until_ready(eng.state)
    log(f"build: {n_params / 1e9:.3f} B params, {N_LANES} lanes x "
        f"{PAGES} pages x {eng.page} tokens, "
        f"{time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    warm_up(eng, sched, PROMPT_LENS, N_NEW)
    log(f"warm-up: {time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds:.1f} s over {clock.n} programs")

    trace = make_trace(cfg.vocab_size, N_REQUESTS, PROMPT_LENS, N_NEW, SEED)
    stats = serve_trace(eng, sched, trace)
    log("serve: " + json.dumps(stats))

    hlo = decode_step_hlo(eng)
    assert "tpu_custom_call" in hlo, "decode step holds no Pallas kernel"
    log("kernel: tpu_custom_call present in the compiled decode step")
    _, P_total, page, KVH, hd = eng.state.k.shape[1:]
    errs = kernel_vs_reference(N_LANES, P_total, page, KVH, hd,
                               cfg.num_heads)
    log(f"kernel vs reference at ({N_LANES}, {P_total}, {page}, {KVH}, "
        f"{hd}) bf16: {json.dumps(errs)} within out {OUT_TOL}, "
        f"relevance {REL_TOL}")

    http = serve_http(sched, make_trace(cfg.vocab_size, N_HTTP, PROMPT_LENS,
                                        N_NEW, seed=SEED + 2))
    log("server: " + json.dumps(http))

    mem = jax.devices()[0].memory_stats() or {}
    log(f"compile: {clock.seconds:.1f} s over {clock.n} programs; "
        f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'not reported')} "
        f"of bytes_limit {mem.get('bytes_limit', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
