"""chip_smoke.py off the chip (tier-1): it must FAIL here, say why, and its
parent must never touch jax — the legs' children own the chip."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_fails_on_cpu_and_names_the_platform(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "jax found platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert proc.stdout == ""          # no result line, not even a false one


def test_smoke_parent_never_imports_jax():
    """Importing the script and running its parent loop (one leg, which
    fails on the CPU) leaves jax out of the process entirely."""
    code = (
        "import sys, chip_smoke\n"
        "assert 'jax' not in sys.modules, 'import pulled jax in'\n"
        "chip_smoke.LEGS = {'device': 120}\n"
        "rc = chip_smoke.run_all()\n"
        "assert rc == 1, rc\n"
        "assert 'jax' not in sys.modules and 'harp_tpu' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
