"""A whole run of each tiny cell in this process, with the paged decode
step on the Pallas kernel in interpret mode: set-up, the measured window
over ``AsyncServingEngine``, the metrics, and the comparison with the
plain reference that decides ``correct``."""
import json

import jax
import pytest

import run
from repro.kernels import ops
from repro.kernels.paged_decode_attn import paged_decode_attention_kernel


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Route the paged decode step through the Pallas kernel, as on a TPU,
    in interpret mode; jit caches are cleared on both sides."""
    traced = []

    def kernel(*args, **kw):
        traced.append(args[0].shape)
        return paged_decode_attention_kernel(*args, interpret=True, **kw)

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "paged_decode_attention_kernel", kernel)
    jax.clear_caches()
    yield traced
    jax.clear_caches()


def run_cell(root, cell, seed=5):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "2", "--trace", "0"], require_tpu=False, root=root)


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_a_sound_run_is_correct(tiny_root, pallas_interpret, capsys, cell):
    r = run_cell(tiny_root, cell, seed=2**31 + 3)
    assert pallas_interpret, "the decode step never traced the kernel"
    assert r["correct"], r
    assert r["compiles_in_window"] == 0
    assert r["reference"]["tokens"] > 0
    m = r["metrics"]
    assert m["decode_tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert ("ttft_p95_s" in m) == (cell == "tiny-open")
    assert r["attempted"] > 0 and r["failed"] == 0
    # the result is the last line of stdout, its checks last
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith('{"correct": true') and \
        list(json.loads(last))[-1] == "checks"


