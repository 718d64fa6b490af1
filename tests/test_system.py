"""End-to-end system tests: the real CLI surfaces.

  * dry-run subprocess: one (arch x shape) cell lowers + compiles on the
    512-device production mesh and emits roofline terms,
  * serve failover: mid-generation promotion produces the identical stream,
  * train CLI: failures + promotion + restart, finite losses.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def test_dryrun_cell_subprocess(tmp_path):
    out = tmp_path / "cell.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--mesh", "single",
         "--out", str(out)],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    res = json.loads(out.read_text())
    assert res[0]["ok"]
    terms = res[0]["terms"]
    assert terms["chips"] == 256
    assert terms["flops_per_device"] > 0
    assert terms["dominant"] in ("compute", "memory", "collective")


def test_dryrun_multipod_cell_subprocess(tmp_path):
    out = tmp_path / "cell.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--mesh", "multi",
         "--out", str(out)],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    res = json.loads(out.read_text())
    assert res[0]["ok"] and res[0]["terms"]["chips"] == 512


def test_serve_failover_identical_stream():
    from repro.launch.serve import ReplicatedServer
    prompts = np.random.default_rng(0).integers(0, 400, (2, 16),
                                                dtype=np.int32)
    a = ReplicatedServer("codeqwen1.5-7b", batch=2, prompt_len=16)
    clean = a.generate(prompts, 8, kill_at=-1)
    b = ReplicatedServer("codeqwen1.5-7b", batch=2, prompt_len=16)
    faulty = b.generate(prompts, 8, kill_at=3)
    np.testing.assert_array_equal(clean, faulty)
    assert b.promotions == 1


def test_serve_without_replication_fails():
    from repro.launch.serve import ReplicatedServer
    prompts = np.zeros((2, 16), dtype=np.int32)
    srv = ReplicatedServer("codeqwen1.5-7b", batch=2, prompt_len=16,
                           replication=False)
    with pytest.raises(RuntimeError):
        srv.generate(prompts, 8, kill_at=2)


def test_train_cli(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "codeqwen1.5-7b", "--steps", "8", "--seq", "32", "--batch", "4",
         "--ft-mode", "combined", "--ckpt-dir", str(tmp_path / "ck"),
         "--ckpt-interval", "3", "--kill", "3:0", "--kill", "6:8"],
        env=dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")),
        cwd=ROOT, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "promotions=1" in proc.stdout
    assert "restarts=1" in proc.stdout
