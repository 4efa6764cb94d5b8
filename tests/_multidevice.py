"""Run multi-device jax snippets in a forced-multi-device subprocess.

The main pytest process keeps the default single CPU device (the rest of
the suite depends on it), and ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` only takes effect before the first jax import — so
anything needing a real multi-device mesh runs as a child interpreter
with the flag set in its environment. Extracted from
tests/test_distributed.py so every multi-device suite (that module and
tests/test_mesh_dataplane.py) shares one helper: env setup, src/ on
PYTHONPATH, a timeout, and both output streams surfaced on failure.
"""

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# 8 forced host devices: enough for a (2, 4) data/model mesh and every
# power-of-two data-shard count the mesh-dataplane tests sweep
DEVICE_COUNT = 8

# generous: child interpreters pay the full jax import + trace cost
TIMEOUT_S = 560


def run_multidevice(
    script: str, *, devices: int = DEVICE_COUNT, timeout: float = TIMEOUT_S
) -> str:
    """Execute ``script`` (dedented) in a child interpreter with
    ``devices`` forced host devices and ``src/`` on PYTHONPATH.

    Returns the child's stdout; a nonzero exit asserts with both streams
    in the failure message so pytest shows the real traceback.
    """
    env = dict(os.environ)
    # forced host devices exist on the CPU backend only, and a child
    # must never reach for a TPU its parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, (
        f"multidevice child failed (rc={out.returncode})\n"
        f"--- stdout ---\n{out.stdout}\n--- stderr ---\n{out.stderr}"
    )
    return out.stdout
