"""rx_offcpu_share (%): of the seconds the edge legs' native receives
took on the threads that made them (a helper, or the loop inline), the
share those threads were off their CPU: the kernel's copies and the
frame scan are CPU, what is left is the wait to have the interpreter
lock back (`pump.fetch.seconds` beside `pump.fetch.cpu_seconds`)."""

import _shares


def read(ctx):
    return _shares.offcpu_share(ctx, "pump.fetch.seconds",
                                "pump.fetch.cpu_seconds")
