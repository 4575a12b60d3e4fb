"""The plain reference: it makes the served model's weights from the seed
by itself, its forward pass agrees with the program's prefill, and its
int8 control fails the tiny cells' limit on every seed tried."""
import numpy as np
import pytest

import harness as H
import jax
from conftest import TINY_CONFIG, TINY_LIMIT

REF = H.load_module(H.BENCH / "references/mistral.py")


@pytest.fixture(scope="module")
def cfg():
    return H.model_config(TINY_CONFIG)


def test_weights_are_the_served_models_from_the_same_seed(cfg):
    prog = H.make_params(TINY_CONFIG, cfg, 1234)
    ref = REF.make_weights(TINY_CONFIG, 1234)
    blocks = prog["blocks"]["l0"]
    pairs = {"wq": blocks["attn"]["wq"], "wk": blocks["attn"]["wk"],
             "wv": blocks["attn"]["wv"], "wo": blocks["attn"]["wo"],
             "w_up": blocks["ffn"]["w_up"], "w_gate": blocks["ffn"]["w_gate"],
             "w_down": blocks["ffn"]["w_down"], "embed": prog["embed"],
             "unembed": prog["unembed"]}
    for name, p in pairs.items():
        np.testing.assert_array_equal(np.asarray(ref[name], np.float32),
                                      np.asarray(p, np.float32), name)
    for name, p in (("norm1", blocks["norm1"]), ("norm2", blocks["norm2"]),
                    ("final_norm", prog["final_norm"])):
        np.testing.assert_array_equal(np.asarray(ref[name]),
                                      np.asarray(p, np.float32) + 1.0, name)


def test_reference_agrees_with_the_programs_prefill(cfg):
    from repro.models import model as MD
    params = H.make_params(TINY_CONFIG, cfg, 7)
    toks = np.random.default_rng(0).integers(0, 512, 200).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        state = MD.init_decode_state(cfg, 1, 256)
        logits, _ = MD.prefill_chunk(params, cfg, jax.numpy.asarray(
            toks[None]), state, 0)
    ref = REF.Reference(TINY_CONFIG, 7, 256, chunk=64)
    best, top, picked = ref.score(toks, [199], [[0]])
    lg = np.asarray(logits[0], np.float32)
    # the program runs bf16 weights and activations; its top token's
    # logit lies within bf16 rounding of the reference's best
    assert best[0] - ref.score(toks, [199], [[int(lg.argmax())]])[2][
        0, 0] < TINY_LIMIT
    assert abs(float(lg.max()) - float(best[0])) < 0.05


@pytest.mark.parametrize("seed", [3, 11, 2**31 + 9])
def test_int8_control_fails_the_limit(seed):
    """The control: the reference with int8 weights, put in the program's
    place at every position of a prompt; its widest gap is past the
    limit the tiny cells are judged by."""
    toks = np.random.default_rng(seed).integers(0, 512, 256).astype(np.int32)
    pos = np.arange(256)
    ctl = REF.Reference(TINY_CONFIG, seed, 256, chunk=64, int8=True)
    _, top, _ = ctl.score(toks, pos, np.zeros((256, 1)))
    ref = REF.Reference(TINY_CONFIG, seed, 256, chunk=64)
    best, _, picked = ref.score(toks, pos, top[:, None])
    assert float((best - picked[:, 0]).max()) > TINY_LIMIT


def test_a_query_attends_the_pages_it_is_given_and_itself():
    """Every page given reads as causal attention; a page struck out (as
    the freeze schedule strikes one) changes what the query reads, and
    the query still attends itself with no page given."""
    toks = np.random.default_rng(4).integers(0, 512, 200).astype(np.int32)
    ref = REF.Reference(TINY_CONFIG, 4, 256, chunk=64, page=16)
    L = TINY_CONFIG["num_hidden_layers"]
    look = [[7]]
    plain = ref.score(toks, [199], look)
    every = ref.score(toks, [199], look, {0: np.ones((L, 13), bool)})
    np.testing.assert_array_equal(plain[0], every[0])
    np.testing.assert_array_equal(plain[2], every[2])
    some = np.ones((L, 13), bool)
    some[1, 3] = False
    struck = ref.score(toks, [199], look, {0: some})
    assert abs(float(struck[0][0]) - float(plain[0][0])) > 1e-6
    alone = ref.score(toks, [199], look, {0: np.zeros((L, 13), bool)})
    assert np.isfinite(alone[0]).all()


def test_visibility_files_each_lanes_pages_by_request_and_position():
    vis = H.Visibility.__new__(H.Visibility)
    vis.page = 4
    # two layers, two lanes, three slots: logical page per slot, -1 for an
    # unmapped or frozen slot
    vis.snaps = [np.array([[[0, 2, -1], [5, -1, 1]],
                           [[-1, 2, 1], [0, 1, 2]]], np.int32)]
    vis.rows = {(7, 9): (0, 1)}
    vis._pending = None
    got = vis.pages(7, 9)            # pages 0..2 (position 9 is on page 2)
    np.testing.assert_array_equal(got, [[False, True, False],
                                        [True, True, True]])
