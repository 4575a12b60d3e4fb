"""Shared set-up for the benchmark's CPU tests: the harness modules under
``bench/`` on the import path, and a throwaway checkout holding a tiny
cell (BENCHMARK.json, configuration, its architecture file, traffic and
limits) that ``bench/run.py``'s ``main`` can run on the CPU."""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

TINY_CONFIG = {
    "name": "tiny", "source": "test", "architecture": "mistral",
    "serve_arch": "mistral-large-123b", "hidden_size": 256, "head_dim": 64,
    "initializer_range": 0.02, "intermediate_size": 512,
    "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 512}
# the two loops of the real mixes at CPU size: a closed loop with freeze
# on whose 256-token prompts overflow a 4-page pool, and an open loop with
# freeze off whose requests fit their pool
TINY_MIXES = {
    "tiny-closed": {
        "serving": {"n_lanes": 2, "max_active_pages": 4, "prefill_chunk": 64,
                    "max_seq": 1024, "enable_freeze": True},
        "loop": "closed", "clients": 3, "followups": 1, "prebuild_wave": 2,
        "prompt": {"dist": "loguniform", "lo": 129, "hi": 256,
                   "round": "pow2"},
        "output": {"dist": "fixed", "value": 40}, "greedy_share": 0.5,
        "temperature": 0.7, "sizes_seed": 1,
        "check": {"requests": 3}},
    "tiny-open": {
        "serving": {"n_lanes": 2, "max_active_pages": 8, "prefill_chunk": 64,
                    "max_seq": 512, "enable_freeze": False},
        "loop": "open", "rate_per_s": 2.0, "warm_s": 1.0,
        "prompt": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                   "clip": [16, 128], "round": "pow2"},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "clip": [4, 24]},
        "greedy_share": 0.5, "temperature": 0.7, "sizes_seed": 2,
        "check": {"requests": 3}},
}
# widest gap of a served greedy token below the reference's best at this
# size (weights of std 0.02 give logits of std ~0.3): sound runs read 0 to
# 0.0070 over six seeds of both cells, every served greedy token compared;
# the int8 control reads 0 to 0.022 at the same positions, and 0.004 to
# 0.023 at every position of a 256-token prompt (test_bench_reference)
TINY_LIMIT = 0.008


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    root = tmp_path_factory.mktemp("checkout")
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    shutil.copytree(ROOT / "bench/archs", root / "bench/archs")
    workloads = []
    for name, mix in TINY_MIXES.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
        (root / f"bench/limits/{name}.json").write_text(json.dumps(
            {"limits": {"max_gap": TINY_LIMIT}}))
        workloads.append({"name": name, "config": "tiny", "traffic": name,
                          "chips": 1, "why": "test"})
    bench = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
             "run_seconds": 2,
             "configs": [{"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": workloads,
             "end_to_end": [
                 {"name": n, "unit": u, "better": b, "bound": 0.05,
                  "source": "host_clock"}
                 for n, u, b in (("decode_tok_s", "tokens/s", "higher"),
                                 ("itl_p95_ms", "ms", "lower"),
                                 ("peak_hbm_gb", "GB", "lower"),
                                 ("setup_s", "s", "lower"))],
             "per_layer": []}
    bench["end_to_end"].insert(2, {
        "name": "ttft_p95_s", "unit": "s", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": ["tiny-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
