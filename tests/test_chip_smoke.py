"""``chip_smoke.py`` on the CPU: it refuses to report a result without a
TPU, and its CPU rehearsal (a 2-layer cut, interpreted kernels) runs every
phase — so a change that breaks the smoke fails here, before it costs a chip
run.  Each case is a child process: the smoke configures JAX for itself."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_ENABLE_COMPILATION_CACHE="false", **env)
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=ROOT, env=full, capture_output=True,
                          text=True, timeout=600)


def _results(stdout):
    return [line for line in stdout.splitlines() if line.startswith("{")]


def test_refuses_without_tpu():
    proc = _run([])
    assert proc.returncode != 0
    assert not _results(proc.stdout)
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("chips,xla_flags", [
    ("1", ""),
    ("4", "--xla_force_host_platform_device_count=4"),
])
def test_cpu_rehearsal_passes(chips, xla_flags):
    proc = _run(["--rehearse", "--chips", chips], XLA_FLAGS=xla_flags)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "rehearsal passed" in proc.stdout
    # a rehearsal is never a device result
    assert not any(json.loads(line).get("ok") for line in
                   _results(proc.stdout))
