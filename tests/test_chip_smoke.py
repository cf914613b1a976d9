"""``chip_smoke.py`` rehearsed on the CPU: its phases at the smoke preset,
with the platform check the only code they skip, and the check itself."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

pytestmark = pytest.mark.slow          # real JAX engines

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def log():
    return chip_smoke.CompileLog()


def test_one_chip_phase_at_smoke_size(log, capsys):
    chip_smoke.phase_one_chip(0, log, smoke=True)
    out = capsys.readouterr().out
    assert "[reference] request 6" in out and "[reference] request 7" in out
    assert "prefix tokens copied 0" in out        # bucketed mode: no radix
    assert "[chunked] served 12 requests" in out


def test_fleet_phase_at_smoke_size(log, capsys, monkeypatch):
    # Four engines share the one CPU device, which keeps no memory stats;
    # on the chip each engine has its own device.
    monkeypatch.setattr(chip_smoke, "bytes_in_use", lambda dev: 1)
    chip_smoke.phase_fleet(0, [jax.devices()[0]] * 4, log, smoke=True)
    out = capsys.readouterr().out
    assert "[fleet] served 12 requests" in out
    assert "match the single engine" in out


def test_fleet_phase_refuses_a_token_the_reference_rules_out(
        log, monkeypatch):
    """A single-engine token that is no near-tie under the reference (its
    argmax's neighbour id, far below the top) fails the phase."""
    monkeypatch.setattr(chip_smoke, "bytes_in_use", lambda dev: 1)
    serve_mode = chip_smoke.serve_mode

    def one_wrong_token(*a, **kw):
        res = serve_mode(*a, **kw)
        res["engine"].output_tokens[3][2] ^= 1
        return res

    monkeypatch.setattr(chip_smoke, "serve_mode", one_wrong_token)
    with pytest.raises(RuntimeError, match="request 3: token 2 differs"):
        chip_smoke.phase_fleet(0, [jax.devices()[0]] * 4, log, smoke=True)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_a_tpu_or_the_repo(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
