"""Dry-run machinery: spec building (no devices needed) + one real
512-device lower/compile in a subprocess (the full 10x4x2 sweep runs via
`python -m repro.launch.dryrun`; its artifacts live in experiments/dryrun)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest
from jax.sharding import AbstractMesh

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch import specs as SP

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


class TestSpecs:
    def test_all_combos_build(self):
        """Every (arch x shape) either builds a StepBundle or is an
        explicit documented skip — nothing falls through."""
        mesh = AbstractMesh((16, 16), ("data", "model"))
        built = skipped = 0
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in INPUT_SHAPES.values():
                if SP.skip_reason(cfg, shape):
                    skipped += 1
                    continue
                bundle = SP.build_step(cfg, shape, mesh)
                assert bundle.fn is not None
                built += 1
        assert built == 39 and skipped == 1   # whisper long_500k only

    def test_long_500k_uses_paged_path(self):
        mesh = AbstractMesh((16, 16), ("data", "model"))
        b = SP.build_step(get_config("mistral-large-123b"),
                          INPUT_SHAPES["long_500k"], mesh)
        assert b.static["kind"] == "decode_paged"
        assert b.static["active_tokens"] == SP.LONG_CONTEXT_ACTIVE_TOKENS

    def test_rwkv_long_500k_is_o1_state(self):
        mesh = AbstractMesh((16, 16), ("data", "model"))
        b = SP.build_step(get_config("rwkv6-1.6b"),
                          INPUT_SHAPES["long_500k"], mesh)
        assert b.static["kind"] == "decode"   # recurrent state, no paging

    def test_infer_mode_heuristic(self):
        mesh = AbstractMesh((16, 16), ("data", "model"))
        small = SP.param_mode(get_config("llama3-8b"),
                              INPUT_SHAPES["decode_32k"], mesh)
        big = SP.param_mode(get_config("jamba-1.5-large-398b"),
                            INPUT_SHAPES["decode_32k"], mesh)
        train = SP.param_mode(get_config("llama3-8b"),
                              INPUT_SHAPES["train_4k"], mesh)
        assert small == "infer" and big == "train" and train == "train"


@pytest.mark.slow
def test_one_real_512_device_compile(tmp_path):
    """whisper-base decode_32k: full lower+compile on the 16x16 mesh in a
    subprocess (XLA_FLAGS must be set before jax init)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper-base",
         "--shape", "decode_32k", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads((tmp_path / "whisper-base__decode_32k__sp.json")
                     .read_text())
    assert rec["ok"] and rec["chips"] == 256
    assert rec["roofline"]["memory_s"] > 0
