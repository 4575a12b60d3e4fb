"""The harness refuses a host without the chip: no result line, a
non-zero exit."""
import subprocess
import sys

import pytest

import harness as H
from conftest import ROOT


def test_check_device_refuses_the_cpu():
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        H.check_device(1)


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu(tmp_path):
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "nemo-longctx-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr
