"""Guard the driver-facing artifacts: __graft_entry__.entry() must jit,
dryrun_multichip must run on a small virtual mesh.  (That a CPU run is
never stood in for a chip is tests/test_chip_smoke.py's and
tests/test_benchmark_cells.py's to hold.)"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    return subprocess.run(args, cwd=REPO, env=env, timeout=timeout,
                          capture_output=True, text=True)


def test_graft_entry_fn_jits():
    sys.path.insert(0, REPO)
    import __graft_entry__ as ge
    import jax

    fn, args = ge.entry()
    loss = jax.jit(fn)(*args)
    import numpy as np
    assert np.isfinite(float(np.asarray(loss)))


@pytest.mark.slow
def test_dryrun_multichip_four_devices():
    proc = _run([sys.executable, "__graft_entry__.py", "4"], timeout=480,
                extra_env={"XLA_FLAGS":
                           "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    oks = [l for l in proc.stdout.splitlines() if l.endswith("OK")]
    assert len(oks) >= 3, proc.stdout  # zero3+tp, pp, pp+zero3, offload
