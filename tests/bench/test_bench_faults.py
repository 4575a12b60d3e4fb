"""Whole runs of the tiny cells with the timed path broken underneath:
each fault a cell can have must read ``correct`` false.  (The paged
decode step runs its reference attention path here; the faults are in
what surrounds it.)"""
import jax.numpy as jnp
import pytest

from repro.models import model as MD
from repro.serving import engine as E
from test_bench_cells import run_cell


def _altered_tokens(monkeypatch):
    """A token altered where it is produced: both samplers return the
    next token id."""
    vocab = 512
    batched, first = E.sample_batched_perlane, E.sample
    monkeypatch.setattr(E, "sample_batched_perlane",
                        lambda *a, **k: (batched(*a, **k) + 1) % vocab)
    monkeypatch.setattr(E, "sample",
                        lambda *a, **k: (first(*a, **k) + 1) % vocab)


def _unchanged_decode_state(monkeypatch):
    """A decode step that returns its state unchanged (no K/V written, no
    freeze or recovery update)."""
    step = MD.decode_step_paged

    def frozen(params, **k):
        logits, _, info = step(params, **k)
        return logits, k["state"], info
    monkeypatch.setattr(MD, "decode_step_paged", frozen)


def _unchanged_prefill_state(monkeypatch):
    """A prefill chunk that returns its cache unchanged."""
    chunk = MD.prefill_chunk

    def frozen(params, **k):
        logits, _ = chunk(params, **k)
        return logits, k["state"]
    monkeypatch.setattr(MD, "prefill_chunk", frozen)


def _half_batch(monkeypatch):
    """Half of the batch left out: the decode step's logits for the upper
    half of the lanes are not computed (zeros)."""
    step = MD.decode_step_paged

    def half(params, **k):
        logits, state, info = step(params, **k)
        B = logits.shape[0]
        keep = (jnp.arange(B) < B // 2)[:, None]
        return jnp.where(keep, logits, 0.0), state, info
    monkeypatch.setattr(MD, "decode_step_paged", half)


# the faults each cell can have and its check must see: both compare
# every served token of the sampled greedy requests (the open cell's
# light load keeps one lane busy, so half a batch is the closed cell's)
FAULTS = [("tiny-closed", _altered_tokens), ("tiny-closed", _half_batch),
          ("tiny-closed", _unchanged_prefill_state),
          ("tiny-closed", _unchanged_decode_state),
          ("tiny-open", _altered_tokens),
          ("tiny-open", _unchanged_decode_state)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            fault):
    fault(monkeypatch)
    r = run_cell(tiny_root, cell)
    assert not r["correct"], r["reference"]
