"""The load generator's process, and the pipe between it and the
harness.

The harness's process holds the chip, so the load generator runs in a
child that never initialises JAX: it is spawned with
``JAX_PLATFORMS=cpu`` and uploads with the system's own client. Both
ends speak one JSON object per line: the harness writes the child's
arguments first, then commands; the child answers on its standard
output, which carries nothing else.
"""
from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Iterator

ROOT = Path(__file__).resolve().parents[1]


class Child:
    """A driver file run as ``<driver>.py --child`` in its own process."""

    def __init__(self, script: Path, args: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, str(script), "--child"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, cwd=str(ROOT))
        self._lines: queue.Queue = queue.Queue()   # lines, then None
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="bench-child-reader")
        self._reader.start()
        self.send(args)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def recv(self, timeout: float) -> dict:
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"load generator silent for {timeout} s") from None
        if line is None:
            raise RuntimeError(
                f"load generator exited with {self.proc.wait()}")
        return json.loads(line)

    def close(self, timeout: float = 60.0) -> None:
        """Close its input, wait for it to end, and end it if it does
        not."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._reader.join(timeout=timeout)


class Parent:
    """The child's end: its arguments, the commands, and its answers."""

    def __init__(self):
        # keep the pipe clean of anything else the child might print
        self._out = sys.stdout
        sys.stdout = sys.stderr
        self._lock = threading.Lock()
        self.args = json.loads(sys.stdin.readline())

    def commands(self) -> Iterator[dict]:
        for line in sys.stdin:
            yield json.loads(line)

    def send(self, msg: dict) -> None:
        with self._lock:
            self._out.write(json.dumps(msg) + "\n")
            self._out.flush()



def upload(client, up, update) -> dict:
    """Send one scheduled upload with the system's client; its record
    on the shared monotonic clock (``acked`` None if it never was)."""
    from repro.serving import IngestError

    rec = {"cid": up.cid, "tenant": up.tenant, "key": up.key,
           "weight": up.weight, "sent": time.monotonic(), "acked": None,
           "error": None}
    try:
        client.write(up.cid, update, weight=float(up.weight),
                     tenant=up.tenant)
        rec["acked"] = time.monotonic()
    except (IngestError, OSError) as exc:
        rec["error"] = repr(exc)
    return rec
