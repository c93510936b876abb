"""Every example runs to completion (subprocess, CPU backend).

Examples are documentation that executes; a broken one is a broken
quick-start.  Each runs in its own interpreter exactly as the docstring
instructs (JAX_PLATFORMS=cpu).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(REPO, "examples"))
    if f.endswith(".py")
)


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    # the routing layer's own overrides pin every engine to the host
    # path, which is what "CPU backend" means for the examples
    env = dict(os.environ, JAX_PLATFORMS="cpu", DAT_DEVICE_HASH="0",
               DAT_DEVICE_CDC="0", DAT_DEVICE_MERKLE="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"{name} failed:\n{out.stderr[-2000:]}"
    assert out.stdout.strip(), f"{name} produced no output"
