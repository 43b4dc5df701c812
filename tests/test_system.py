"""End-to-end behaviour tests for the paper's system.

1. The offline scheduler + simulator reproduce the paper's headline claims
   (tested in detail in test_scheduler_sim.py).
2. The training launcher runs, checkpoints, and resumes deterministically.
3. The serving launcher prefills + decodes (resident and streaming modes).
4. A dry-run smoke cell lowers + compiles on a forced-512-device mesh and
   emits roofline terms (subprocess: device count is locked at jax init).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=900):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-m", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_launcher_runs_and_resumes(tmp_path):
    ck = str(tmp_path / "ck")
    r = _run(["repro.launch.train", "--arch", "minicpm-2b", "--smoke",
              "--steps", "4", "--batch", "2", "--seq", "32",
              "--ckpt-dir", ck, "--ckpt-every", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step     3" in r.stdout
    # resume: runs steps 4..5 only
    r2 = _run(["repro.launch.train", "--arch", "minicpm-2b", "--smoke",
               "--steps", "6", "--batch", "2", "--seq", "32",
               "--ckpt-dir", ck])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 4" in r2.stdout
    assert "step     3" not in r2.stdout


def test_serve_launcher_prefill_decode():
    r = _run(["repro.launch.serve", "--arch", "gemma-7b", "--smoke",
              "--batch", "2", "--prompt-len", "16", "--gen", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "prefill" in r.stdout and "decode" in r.stdout


def test_serve_streaming_mode():
    r = _run(["repro.launch.serve", "--arch", "minicpm-2b", "--smoke",
              "--streaming", "--arena-slots", "2", "--batch", "2",
              "--prompt-len", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "streaming forward" in r.stdout


@pytest.mark.slow
def test_dryrun_smoke_cell(tmp_path):
    out = str(tmp_path / "cell.json")
    r = _run(["repro.launch.dryrun", "--arch", "minicpm-2b",
              "--shape", "train_4k", "--smoke", "--out", out], timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    cell = json.load(open(out))
    assert cell["chips"] == 256
    roof = cell["roofline"]
    assert roof["dominant"] in ("compute", "memory", "collective")
    assert roof["flops_per_device"] > 0
    assert cell["memory_analysis"]["temp_size_in_bytes"] is not None


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir_rule(env_dir, tmp_path):
    """Entry points keep JAX's persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, setting no other directory; without it,
    at one fixed path inside the checkout."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax; from repro.launch.compile_cache import "
            "setup_compile_cache; d = setup_compile_cache(); "
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    chosen, configured = r.stdout.split()
    want = (str(tmp_path / env_dir) if env_dir is not None else
            os.path.realpath(os.path.join(ROOT, ".jax_cache")))
    assert chosen == configured == want
