"""The host protocol surface must not initialize a jax backend at import.

Session consumers (decoders in network daemons, CLI tools) import the
package and the runtime helpers; backend initialization at import time
costs seconds always, and on a chip host it would take the chip from
the one process that should own it.  Device backends must come up
lazily at first device use.  (The invariant is "no backend init", not
"no jax import".)
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_package_and_runtime_import_without_backend_init():
    code = (
        "import sys\n"
        "import dat_replication_protocol_tpu as protocol\n"
        "from dat_replication_protocol_tpu.runtime import (\n"
        "    TreeSyncSession, content_address, replay_log, tree_sync)\n"
        "from dat_replication_protocol_tpu.session import aio, transport\n"
        "e, d = protocol.encode(), protocol.decode()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, (\n"
        "    f'import initialized backends: {list(xla_bridge._backends)}')\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"
