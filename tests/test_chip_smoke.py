"""chip_smoke.py refuses to run anywhere but on a TPU, and says why.

The smoke's result line is a claim about a device, so a host without a TPU
(or a process told to swap the serving path for the jnp reference) must exit
non-zero and print no result line. Runs the script as a subprocess, the way
it is run on the chip.
"""
import os
import subprocess
import sys

import pytest

from conftest import REPO


@pytest.mark.parametrize("env_extra,needle", [
    ({"JAX_PLATFORMS": "cpu"}, "JAX backend is 'cpu', not 'tpu'"),
    ({"JAX_PLATFORMS": "cpu", "REPRO_FFT_BACKEND": "xla"},
     "REPRO_FFT_BACKEND is set"),
])
def test_chip_smoke_refuses_without_a_tpu(env_extra, needle):
    env = dict(os.environ)
    env.pop("REPRO_FFT_BACKEND", None)
    env.update(env_extra)
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert needle in res.stderr, res.stderr[-2000:]
    assert '"ok"' not in res.stdout
