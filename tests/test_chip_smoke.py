"""chip_smoke.py's contract off the chip: it refuses to report without a
TPU or outside a checkout, and its CPU rehearsal runs every phase at the
reduced size without printing a result line.  Each case runs the script
in a child process on the CPU backend, as a user would."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, *, cwd=ROOT, devices=0):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _no_result(res):
    return '"ok"' not in res.stdout


def test_chip_smoke_refuses_without_tpu():
    res = _run([SCRIPT])
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert _no_result(res)


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone)
    res = _run([str(alone / "chip_smoke.py")], cwd=alone)
    assert res.returncode != 0
    assert "no repro package" in res.stderr
    assert _no_result(res)


@pytest.mark.parametrize("four_chips", [False, True])
def test_chip_smoke_cpu_rehearsal(four_chips):
    args = [SCRIPT, "--rehearse"] + (["--four-chips"] if four_chips else [])
    res = _run(args, devices=4 if four_chips else 0)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "rehearsal: no result line" in res.stdout
    assert _no_result(res)
    if four_chips:
        assert "(4,1) vs single device: max abs diff 0.000e+00" in res.stdout
    else:
        assert "dso_packed_segments" in res.stdout
