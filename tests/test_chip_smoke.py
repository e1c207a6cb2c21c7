"""chip_smoke.py on the CPU: its phases at tiny sizes (the same code the
chip runs at full size), and its refusal to run without a TPU."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    """A device stand-in that reports memory (CPU devices report none)."""

    def __init__(self, dev):
        self.dev = dev

    def memory_stats(self):
        return {"bytes_in_use": 1 << 30, "peak_bytes_in_use": 1 << 30}


def test_batch_phase_tiny(capsys):
    chip_smoke.batch_phase("rmat:scale=9,ef=8,seed=1", 128)
    out = capsys.readouterr().out
    assert "match bfs_scipy" in out and "128 lanes" in out


def test_serve_phase_tiny(capsys):
    chip_smoke.serve_phase("rmat:scale=9,ef=8,seed=1", n_requests=5,
                           lanes=64, ladder="32,64")
    assert "5 requests ok" in capsys.readouterr().out


def test_four_chip_phase_tiny_on_virtual_devices(capsys):
    devs = [_Dev(d) for d in jax.devices()[:4]]
    chip_smoke.four_chip_phase(devs, "rmat:scale=9,ef=8,seed=1", 128)  # 4096 lanes
    out = capsys.readouterr().out
    assert "bit-identical" in out and "each on 4 distinct devices" in out
    assert "Dist2DBfsEngine" in out


def test_spread_lanes_hits_distinct_words():
    import numpy as np

    lanes = chip_smoke.spread_lanes(np.full(8192, 5))
    assert lanes[0] == 0 and lanes[-1] == 8191
    assert len({i // 32 for i in lanes}) == 4


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    cwd = REPO
    if where == "alone":
        cwd = tmp_path
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line
