"""What a configuration file's published keys mean comes from its
architecture file (``bench/archs/<architecture>.py``), found by name: the
program's ``ModelConfig`` and the counts ``bench/work`` needs.  Mistral's
file gives the very config and counts the harness gave before it was
split out, and a non-dense configuration joins as new files alone."""
import json
import pathlib

import numpy as np
import pytest

import harness as H
from conftest import TINY_CONFIG
from repro.configs.base import FreezeConfig, ModelConfig

ROOT = pathlib.Path(__file__).resolve().parents[2]
NEMO = json.loads((ROOT / "bench/configs/mistral-nemo-12b.json").read_text())
# launch/serve.py's schedule for mistral-large-123b, the serve_arch of both
FREEZE = FreezeConfig(window=16, k_soft=1.0, tau_mode="quantile",
                      quantile=0.45, entropy_abs_threshold=1e9,
                      recovery_enabled=True)
BUILT = {
    "mistral-nemo-12b": (NEMO, ModelConfig(
        name="mistral-nemo-12b", arch_type="dense", num_layers=10,
        d_model=5120, num_heads=32, num_kv_heads=8, d_ff=14336,
        vocab_size=131072, head_dim=128, rope_theta=1000000.0,
        norm_eps=1e-05, dtype="bfloat16", tie_embeddings=False,
        source="https://huggingface.co/mistralai/Mistral-Nemo-Base-2407",
        freeze=FREEZE)),
    "tiny": (TINY_CONFIG, ModelConfig(
        name="tiny", arch_type="dense", num_layers=2, d_model=256,
        num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=512, head_dim=64,
        rope_theta=1000000.0, norm_eps=1e-05, dtype="bfloat16",
        tie_embeddings=False, source="test", freeze=FREEZE)),
}


@pytest.mark.parametrize("name", sorted(BUILT))
def test_mistral_file_builds_the_model_config_field_for_field(name):
    c, want = BUILT[name]
    assert H.model_config(c) == want


def test_mistral_counts_for_nemo_are_the_published_shapes():
    a = H.arch(NEMO)
    # per layer: wq+wo 2*5120*32*128, wk+wv 2*5120*8*128, MLP 3*5120*14336;
    # ten layers and the 5120 x 131072 head
    assert a.matmul_params(NEMO) == 10 * 272_629_760 + 671_088_640 \
        == 3_397_386_240
    assert a.attn_flops_per_token(NEMO) == 4 * 32 * 128 == 16_384
    assert a.kv_bytes_per_token(NEMO, 2) == 8 * 128 * 2 * 2 == 4_096
    assert a.q_out_bytes_per_lane(NEMO, 2) == 2 * 32 * 128 * 2 == 16_384
    assert a.kv_scale_bytes_per_page(NEMO) == 2 * 8 * 4 == 64
    work = H.work_modules()
    assert work.decode_step.flops(NEMO, 16, 1000.0) == \
        2.0 * 16 * 3_397_386_240 + 16_384 * 1000.0 == 108_732_743_680.0
    w = work.paged_decode_attn.work(NEMO, visible=3000.0, calls=10,
                                    lanes=16, pages=51, page=64)
    # K/V 3000 * 4096; a call's q and output 16 * 16384, relevance,
    # tables and slot mask 16 * 51 * (4 + 9 + 64), scales 16 * 51 * 64
    assert 3000 * 4_096 + 10 * 377_200 == 16_060_000
    assert w == {"flops": 49_152_000.0, "bytes": 16_060_000.0}


@pytest.mark.parametrize("c", [dict(TINY_CONFIG, architecture="nosuch"),
                               {k: v for k, v in TINY_CONFIG.items()
                                if k != "architecture"}],
                         ids=["unknown", "unnamed"])
def test_an_unknown_architecture_names_the_missing_file(c):
    with pytest.raises(SystemExit, match=r"bench/archs/\w+\.py"):
        H.model_config(c)


# a sparse-experts architecture, as a later configuration would bring it:
# OLMoE's published keys, read by its own file
MOE_ARCH = '''
def model_config(c, freeze):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], arch_type="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        num_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"], tie_embeddings=c["tie_word_embeddings"],
        source=c["source"], freeze=freeze)
'''
MOE_CONFIG = {
    "name": "tiny-moe", "source": "test", "architecture": "olmoe",
    "serve_arch": "mistral-large-123b", "hidden_size": 128,
    "initializer_range": 0.02, "intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "num_experts": 4, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "vocab_size": 256}
MOE_MIX = {"serving": {"n_lanes": 2, "max_active_pages": 4,
                       "prefill_chunk": 64, "max_seq": 512,
                       "enable_freeze": True}}


def test_a_moe_configuration_joins_as_new_files_alone(tmp_path):
    """A checkout that adds only a configuration file, its architecture
    file and a traffic file: the harness builds the MoE model from them
    and the paged engine serves it greedily across page boundaries."""
    from repro.launch import serve
    from repro.serving.sampling import SamplingParams
    from repro.serving.scheduler import Scheduler
    for d in ("configs", "archs", "traffic"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench/configs/tiny-moe.json").write_text(
        json.dumps(MOE_CONFIG))
    (tmp_path / "bench/archs/olmoe.py").write_text(MOE_ARCH)
    (tmp_path / "bench/traffic/moe-decode.json").write_text(
        json.dumps(MOE_MIX))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-moe",
                     "file": "bench/configs/tiny-moe.json"}],
        "workloads": [{"name": "moe-decode", "config": "tiny-moe",
                       "traffic": "moe-decode", "chips": 1}]}))
    cell = H.load_cell("moe-decode", tmp_path)
    cfg = H.model_config(cell.config, cell.root)
    assert cfg.arch_type == "moe" and cfg.num_experts == 4
    assert all(cfg.is_moe_layer(l) for l in range(cfg.num_layers))
    params = H.make_params(cell.config, cfg, 3)
    eng = serve.build_engine(cfg, params, H.serving_config(cell.mix, 3))
    sched = Scheduler(eng)
    prompt = np.random.default_rng(3).integers(0, 256, 128, dtype=np.int32)
    uid = sched.submit(prompt, 70, SamplingParams.greedy())
    sched.run()
    toks = np.asarray(sched.done[uid].result)
    # 128 + 70 positions run over three 64-token page boundaries
    assert toks.shape == (70,) and ((0 <= toks) & (toks < 256)).all()
