"""Parent <-> child messages. The parent (no JAX) generates load and takes
the end-to-end metrics; the child owns the chips and the system under
test. The child gets commands as JSON lines on stdin and answers as JSON
lines on a pipe of its own (its stdout goes to the parent's stderr, since
the program prints there)."""

from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time


class ChildDied(Exception):
    pass


class Child:
    """Parent side: start the child, send commands, wait for answers."""

    def __init__(self, argv: list[str], env: dict[str, str], cwd: str):
        r, w = os.pipe()
        env = dict(env, BENCH_PROTO_FD=str(w))
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=sys.stderr.fileno(),
            stderr=sys.stderr.fileno(), pass_fds=[w], env=env, cwd=cwd,
            start_new_session=True)
        os.close(w)
        self._q: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, args=(r,),
                                        daemon=True)
        self._reader.start()

    def _read(self, fd: int) -> None:
        with os.fdopen(fd) as f:
            for line in f:
                line = line.strip()
                if line:
                    self._q.put(json.loads(line))
        self._q.put(None)   # EOF: the child is gone

    def send(self, **msg) -> None:
        self.proc.stdin.write((json.dumps(msg) + "\n").encode())
        self.proc.stdin.flush()

    def expect(self, kind: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise ChildDied(f"no {kind!r} from the child in {timeout:.0f}s")
            try:
                msg = self._q.get(timeout=left)
            except queue.Empty:
                continue
            if msg is None:
                raise ChildDied(f"the child ended (exit code "
                                f"{self.proc.wait()}) before {kind!r}")
            if msg.get("kind") == "failed":
                raise ChildDied(f"the child failed: {msg.get('why')}")
            if msg.get("kind") == kind:
                return msg

    def ask(self, kind: str, timeout: float, **msg) -> dict:
        self.send(kind=kind, **msg)
        return self.expect(kind, timeout)

    def close(self) -> int:
        """Stop the child and everything it started; wait until it has
        ended."""
        import signal

        try:
            if self.proc.poll() is None:
                try:
                    self.send(kind="quit")
                    self.proc.stdin.close()
                except (BrokenPipeError, OSError):
                    pass
                try:
                    self.proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
        finally:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        return self.proc.wait()


class Link:
    """Child side."""

    def __init__(self):
        self._out = os.fdopen(int(os.environ["BENCH_PROTO_FD"]), "w",
                              buffering=1)

    def say(self, kind: str, **msg) -> None:
        self._out.write(json.dumps(dict(msg, kind=kind)) + "\n")
        self._out.flush()

    def commands(self):
        for line in sys.stdin:
            line = line.strip()
            if line:
                yield json.loads(line)
