"""The operations and bytes ``bench/work`` counts, against hand-computed
shapes of both configurations."""
import json
import pathlib

import pytest

import harness as H

ROOT = pathlib.Path(__file__).resolve().parents[2]


# Mistral-Large-Instruct-2407's published widths at 3 of 88 layers, the
# second configuration the decode-step count is written for
LARGE = {"architecture": "mistral", "hidden_size": 12288,
         "num_attention_heads": 96, "num_key_value_heads": 8, "head_dim": 128,
         "intermediate_size": 28672, "num_hidden_layers": 3,
         "vocab_size": 32768}


def cfg(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def test_decode_step_counts_per_configuration():
    work = H.work_modules()
    nemo, large = cfg("mistral-nemo-12b"), LARGE
    # nemo: wq+wo 2*5120*32*128, wk+wv 2*5120*8*128, MLP 3*5120*14336
    layer = 2 * 20_971_520 + 2 * 5_242_880 + 220_200_960
    assert layer == 272_629_760
    assert work.decode_step.matmul_params(nemo) == \
        10 * layer + 5120 * 131072
    # large: wq+wo 2*12288*96*128, wk+wv 2*12288*8*128, MLP 3*12288*28672
    layer = 2 * 150_994_944 + 2 * 12_582_912 + 1_056_964_608
    assert layer == 1_384_120_320
    assert work.decode_step.matmul_params(large) == \
        3 * layer + 12288 * 32768
    # 16 lane-steps and 1000 visible tokens: 2 FLOPs per weight and lane,
    # QK and PV 4 * H * hd per visible token
    assert work.decode_step.flops(nemo, 16, 1000) == pytest.approx(
        2 * 16 * (10 * 272_629_760 + 671_088_640) + 4 * 32 * 128 * 1000)


def test_paged_attention_needs_visible_kv_and_tables():
    work = H.work_modules()
    nemo = cfg("mistral-nemo-12b")
    w = work.paged_decode_attn.work(nemo, visible=3000, calls=10, lanes=16,
                                    pages=51, page=64)
    kv = 3000 * 8 * 128 * 2 * 2                   # K and V, bf16
    per_call = (2 * 16 * 32 * 128 * 2 + 16 * 51 * 4 + 16 * 51 * 9
                + 16 * 51 * 64 + 16 * 51 * 2 * 8 * 4)
    assert w["bytes"] == kv + 10 * per_call
    assert w["flops"] == 4 * 32 * 128 * 3000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.paged_decode_attn.least_seconds(w, peaks)
    assert bound == "memory" and t == pytest.approx(w["bytes"] / 819e9)


def test_peaks_come_from_the_table_and_an_unknown_device_is_an_error():
    p = H.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(SystemExit, match="no peaks"):
        H.peaks("cpu")
