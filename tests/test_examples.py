"""Every example script runs end-to-end (reference model: doc example
testing — examples that rot are worse than none)."""

import os
import subprocess
import sys

import pytest

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


@pytest.mark.parametrize("script", sorted(
    f for f in os.listdir(_EXAMPLES) if f.endswith(".py")))
def test_example_runs(script):
    env = dict(os.environ)
    repo_root = os.path.dirname(_EXAMPLES)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "PYTHONPATH": repo_root + os.pathsep +
                env.get("PYTHONPATH", "")})
    out = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, script)],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, \
        f"{script} failed:\nstdout:\n{out.stdout[-2000:]}\n" \
        f"stderr:\n{out.stderr[-2000:]}"
    assert f"EXAMPLE_OK {script[:-3]}" in out.stdout
