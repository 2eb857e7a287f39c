"""`chip_smoke.py` refuses to pass anywhere but on a TPU.

The script is the quickest proof that the main path runs on the chip, so a
run on the CPU, or a copy of the script without the package beside it, must
exit non-zero and must not print the ok line.
"""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_smoke(script, cwd, env):
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("where", ["repo-on-cpu", "script-alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    if where == "repo-on-cpu":
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = _run_smoke(os.path.join(ROOT, "chip_smoke.py"), tmp_path, env)
        assert "device: platform=cpu" in out.stdout
        assert "needs a TPU" in out.stderr
    else:
        lone = tmp_path / "lone"
        lone.mkdir()
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), lone)
        out = _run_smoke(str(lone / "chip_smoke.py"), lone, env)
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1], out.stdout
