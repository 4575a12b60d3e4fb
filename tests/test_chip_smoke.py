"""chip_smoke.py's phases, driven on the CPU at llama3-8b-tiny.

The script's ``main()`` demands a TPU; its phase functions do not.  Here
the paged decode step is steered onto the Pallas kernel in interpret mode,
so the same control flow the chip runs (build, warm-up, a trace that
stashes and swaps pages, the kernel check, the HTTP server) is exercised
end to end on a tiny model.
"""
import importlib.util
import pathlib

import jax
import pytest

from repro.kernels import ops
from repro.kernels.paged_decode_attn import paged_decode_attention_kernel
from repro.launch import serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Route the paged decode step through the Pallas kernel, as on a TPU,
    in interpret mode.  Jit caches are cleared on both sides so no trace
    of the reference path is reused, and none of the kernel path leaks."""
    traced = []

    def kernel(*args, **kw):
        traced.append(args[0].shape)
        return paged_decode_attention_kernel(*args, interpret=True, **kw)

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "paged_decode_attention_kernel", kernel)
    jax.clear_caches()
    yield traced
    jax.clear_caches()


def test_main_refuses_a_host_without_tpu(smoke):
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        smoke.check_device()


def test_phases_on_tiny_model(smoke, pallas_interpret):
    cfg = serve.model_config("llama3-8b", tiny=True)
    params = serve.init_model(cfg, smoke.SEED)
    # 4 active pages of 64 tokens: the 200-token prompts overflow the pool
    eng, sched = smoke.build(cfg, params, n_lanes=2, pages=4, max_seq=512,
                             prefill_chunk=64)
    lens, n_new = (64, 128, 200), 8
    smoke.warm_up(eng, sched, lens, n_new)
    stats = smoke.serve_trace(
        eng, sched, smoke.make_trace(cfg.vocab_size, 4, lens, n_new,
                                     smoke.SEED))
    assert stats["requests"] == 4 and stats["tokens"] == 4 * n_new
    assert stats["swap_out"] > 0, stats
    assert pallas_interpret, "the decode step never traced the kernel"
    assert "ENTRY" in smoke.decode_step_hlo(eng)
    B, P, page, KVH, hd = eng.state.k.shape[1:]
    smoke.kernel_vs_reference(B, P, page, KVH, hd, cfg.num_heads,
                              interpret=True)
    http = smoke.serve_http(sched, smoke.make_trace(
        cfg.vocab_size, 2, lens, n_new, smoke.SEED + 2))
    assert http == {"http_requests": 2, "unhandled_exceptions": 0}
