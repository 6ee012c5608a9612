"""``chip_smoke.py`` refuses to run without a TPU and never reports success.

It has no CPU fallback: with JAX held to the CPU it must exit nonzero and
print no ``"ok": true`` line, both from the repository root and from a
directory that holds the script and nothing else of the repository.
"""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.mark.parametrize("where", ["repo_root", "script_alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if where == "script_alone":
        script = shutil.copy(script, tmp_path)
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert '"ok": true' not in proc.stdout
