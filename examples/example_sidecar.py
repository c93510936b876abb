"""A foreign client piping into the sidecar daemon.

The "client" below writes RAW wire bytes to a TCP socket — no package
Encoder — exactly what a non-Python process speaking the dat
replication wire format would send (the reference's deployment shape,
reference: example.js:53 `encode.pipe(socket)`).  The sidecar decodes
the session, content-hashes the change payload and the blob through
the routed digest engine, and streams a digest session back.

Run: python examples/example_sidecar.py
"""

import socket
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import dat_replication_protocol_tpu as protocol  # noqa: E402
from dat_replication_protocol_tpu import sidecar  # noqa: E402
from dat_replication_protocol_tpu.utils.cache import (  # noqa: E402
    enable_compile_cache,
)


def main() -> None:
    enable_compile_cache()  # serve_tcp in-process skips sidecar.main()
    ready = threading.Event()
    port = {}
    threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=1,
                    ready_cb=lambda p: (port.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    ).start()
    ready.wait(10)

    # hand-framed wire bytes (varint(len+1) | id | payload):
    # one change {key:'key', change:1, from:0, to:1, value:'hello'}
    # and one 11-byte blob, as a foreign client would emit them
    change_payload = bytes.fromhex(
        "12036b6579" "1801" "2000" "2801" "320568656c6c6f")
    wire = (bytes([len(change_payload) + 1, 0x01]) + change_payload
            + bytes([0x0C, 0x02]) + b"hello world")

    c = socket.create_connection(("127.0.0.1", port["p"]), timeout=10)
    c.sendall(wire)
    c.shutdown(socket.SHUT_WR)
    raw = b""
    while True:
        d = c.recv(65536)
        if not d:
            break
        raw += d
    c.close()

    dec = protocol.decode()
    dec.change(lambda ch, done: (
        print(f"digest reply: {ch.key} ({ch.subset}) = "
              f"{ch.value.hex()[:16]}…"),
        done(),
    ))
    dec.write(raw)
    dec.end()
    assert dec.finished


if __name__ == "__main__":
    main()
