"""Child processes for the multi-device tests.

A mesh test needs several devices, which the CPU backend only provides
when ``XLA_FLAGS`` is set before JAX starts -- so those tests run their
code in a child process.  The child is pinned to the CPU backend here,
never by inheritance: a child that reached for an accelerator would
contend with its parent for the chip.
"""
from __future__ import annotations

import os
import subprocess
import sys

# prepended to every child script: the source tree and the mesh helper
_PRELUDE = ('import sys; sys.path.insert(0, "src")\n'
            'from repro.launch.mesh import make_mesh\n')


def child_env(devices: int = 0) -> dict:
    """The parent environment with the CPU backend forced and, when
    ``devices`` is set, that many virtual host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return env


def run_devices(code: str, devices: int = 8, tail: int = 1500) -> str:
    """Run ``code`` in a CPU child with ``devices`` virtual devices (and
    ``make_mesh`` in scope); assert it exits 0 and return its stdout."""
    p = subprocess.run([sys.executable, "-c", _PRELUDE + code],
                       capture_output=True, text=True, cwd=".",
                       env=child_env(devices))
    assert p.returncode == 0, p.stderr[-tail:]
    return p.stdout
