"""The child processes of a run: the sidecar that owns the chip, and the
load-generator clients.  The parent that drives them never imports jax.

`Sidecar` began as a copy of `chip_smoke.py`'s (PR 21, proven on the
chip); it differs in running dark unless told otherwise, and in the
control channel to `serve.py`.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """A run that cannot report: the message is the cause."""


def cpu_seconds(pid: int) -> float:
    """utime + stime of a process, all its threads, from /proc."""
    with open(f"/proc/{pid}/stat") as f:
        rest = f.read().rsplit(")", 1)[1].split()
    return (int(rest[11]) + int(rest[12])) / os.sysconf("SC_CLK_TCK")


class Sidecar:
    """One `serve.py` child.  Its stderr is scanned for the device line
    and the port; its stdout carries the control thread's answers; with
    telemetry on, its stats pipe is tailed (a supervisor that stops
    reading makes the emitter tear a record and latch dead)."""

    def __init__(self, flags: list[str], telemetry: bool, env: dict):
        argv = [sys.executable, os.path.join(HERE, "serve.py"),
                "--tcp", "127.0.0.1:0", *flags]
        pass_fds = ()
        self._stats_r = None
        if telemetry:
            # exactly as chip_smoke.py passes them
            r, w = os.pipe()
            os.set_inheritable(w, True)
            argv += ["--stats-fd", str(w), "--stats-interval", "1",
                     "--obs-http", "0"]
            pass_fds = (w,)
            self._stats_r = r
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, pass_fds=pass_fds, close_fds=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        if telemetry:
            os.close(w)
        self.port = None
        self.device_line = None
        self.t_listening = None
        self.stderr_tail: list[str] = []
        self.snapshots: list[dict] = []
        self.answers: list[dict] = []
        self._cv = threading.Condition()
        self._threads = [
            threading.Thread(target=self._tail_stderr, daemon=True),
            threading.Thread(target=self._tail_stdout, daemon=True)]
        if telemetry:
            self._threads.append(
                threading.Thread(target=self._tail_stats, daemon=True))
        for t in self._threads:
            t.start()

    def _tail_stderr(self) -> None:
        for line in self.proc.stderr:
            line = line.rstrip("\n")
            with self._cv:
                self.stderr_tail.append(line)
                del self.stderr_tail[:-40]
                m = re.search(r"listening on \S*:(\d+)$", line)
                if m and "obs" not in line:
                    self.port = int(m.group(1))
                    self.t_listening = time.monotonic()
                if line.startswith("sidecar: device "):
                    self.device_line = line
                self._cv.notify_all()

    def _tail_stdout(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("ctl: "):
                with self._cv:
                    self.answers.append(json.loads(line[5:]))
                    self._cv.notify_all()

    def _tail_stats(self) -> None:
        buf = b""
        while True:
            chunk = os.read(self._stats_r, 1 << 16)
            if not chunk:
                return
            buf += chunk
            *lines, buf = buf.split(b"\n")
            with self._cv:
                for ln in lines:
                    if ln.strip():
                        self.snapshots.append(json.loads(ln))
                self._cv.notify_all()

    def tail(self, n: int = 12) -> str:
        return "\n".join(self.stderr_tail[-n:])

    def wait_for(self, pred, timeout: float, what: str):
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                got = pred()
                if got:
                    return got
                if self.proc.poll() is not None:
                    raise BenchFailure(
                        f"the sidecar exited {self.proc.returncode} "
                        f"before {what}")
                left = deadline - time.monotonic()
                if left <= 0:
                    raise BenchFailure(
                        f"timed out after {timeout:.0f} s waiting for "
                        f"{what}")
                self._cv.wait(min(left, 0.5))

    def wait_listening(self, timeout: float) -> dict:
        """Block until the sidecar listens; return its device line as a
        record (`sidecar: device engine="…" platform="…" …`, printed
        with telemetry dark)."""
        self.wait_for(lambda: self.port and self.device_line, timeout,
                   "the sidecar's device line and port")
        return {k: json.loads(v) for k, v in re.findall(
            r'(\w+)=("[^"]*"|[^\s"]+)', self.device_line)}

    def ask(self, cmd: dict, timeout: float) -> dict:
        """One control command, one answer; the wait is bounded and a
        failed command is a failed run."""
        seen = len(self.answers)
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        got = self.wait_for(lambda: self.answers[seen:], timeout,
                         f"the answer to {cmd['cmd']!r}")[0]
        if not got.get("ok"):
            raise BenchFailure(f"{cmd['cmd']} failed in the sidecar: "
                               f"{got.get('error')}")
        return got

    def snapshot_near(self, t: float) -> dict | None:
        """The stats snapshot whose monotonic stamp is nearest `t`."""
        with self._cv:
            snaps = [s for s in self.snapshots if "monotonic" in s]
        return min(snaps, key=lambda s: abs(s["monotonic"] - t),
                   default=None)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=15)
        for t in self._threads:
            t.join(timeout=5)
        for f in (self.proc.stdin, self.proc.stdout, self.proc.stderr):
            try:
                f.close()
            except OSError:
                pass
        if self._stats_r is not None:
            os.close(self._stats_r)
            self._stats_r = None


class Client:
    """One `client.py` child: a line of JSON in, a line of JSON out."""

    def __init__(self, spec: dict, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"),
             json.dumps(spec)],
            cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        self.lines: list[dict] = []
        self.stderr_tail: list[str] = []
        self._cv = threading.Condition()
        self._threads = [
            threading.Thread(target=self._tail_stdout, daemon=True),
            threading.Thread(target=self._tail_stderr, daemon=True)]
        for t in self._threads:
            t.start()

    def _tail_stdout(self) -> None:
        for line in self.proc.stdout:
            if line.strip():
                with self._cv:
                    self.lines.append(json.loads(line))
                    self._cv.notify_all()

    def _tail_stderr(self) -> None:
        for line in self.proc.stderr:
            self.stderr_tail.append(line.rstrip("\n"))
            del self.stderr_tail[:-20]

    def send(self, cmd: dict) -> int:
        """Send a command; the ticket is what `answer` waits on."""
        with self._cv:
            ticket = len(self.lines)
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        return ticket

    def answer(self, ticket: int, timeout: float, what: str) -> dict:
        deadline = time.monotonic() + timeout
        with self._cv:
            while len(self.lines) <= ticket:
                if self.proc.poll() is not None:
                    raise BenchFailure(
                        f"a client process exited {self.proc.returncode} "
                        f"before {what}:\n" + "\n".join(self.stderr_tail))
                left = deadline - time.monotonic()
                if left <= 0:
                    raise BenchFailure(f"timed out after {timeout:.0f} s "
                                       f"waiting for {what}")
                self._cv.wait(min(left, 0.5))
            return self.lines[ticket]

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()    # ends its command loop
            except OSError:
                pass
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        for t in self._threads:
            t.join(timeout=5)
        for f in (self.proc.stdout, self.proc.stderr):
            f.close()
