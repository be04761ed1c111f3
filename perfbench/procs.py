"""The processes of one run: the server under test, the miner host, and the
checks that the chip is free again.  Nothing here imports jax."""

from __future__ import annotations

import glob
import json
import os
import re
import signal
import socket
import subprocess
import threading
import time
from pathlib import Path
from typing import List, Optional

ROOT = Path(os.path.abspath(__file__)).parents[1]  # the checkout
HERE = Path(os.path.abspath(__file__)).parent


class Failed(Exception):
    """The run cannot produce a result."""


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One subprocess; a reader thread per output stream keeps its lines
    with their arrival times.  ``stdin=True`` keeps a pipe to write
    commands to."""

    def __init__(self, name: str, argv: List[str], env: dict, cwd: Path,
                 stdin: bool = False) -> None:
        self.name = name
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, text=True,
            stdin=subprocess.PIPE if stdin else subprocess.DEVNULL,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        self.lines: List = []  # (time, stream, line)
        self._cv = threading.Condition()
        self._readers = [
            threading.Thread(target=self._read, args=(s, tag), daemon=True)
            for s, tag in ((self.proc.stdout, "out"), (self.proc.stderr, "err"))
        ]
        for t in self._readers:
            t.start()

    def _read(self, stream, tag: str) -> None:
        for line in stream:
            with self._cv:
                self.lines.append((time.monotonic(), tag, line.rstrip("\n")))
                self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def wait_line(self, pattern: str, timeout: float, start: int = 0,
                  stream: Optional[str] = None):
        """The first line at index >= ``start`` matching ``pattern``:
        ``(index, arrival time, match)``."""
        rx = re.compile(pattern)
        end = time.monotonic() + timeout
        i = start
        with self._cv:
            while True:
                while i < len(self.lines):
                    _, tag, line = self.lines[i]
                    m = rx.search(line) if stream in (None, tag) else None
                    if m:
                        return i, self.lines[i][0], m
                    i += 1
                if self.proc.poll() is not None and not any(
                    t.is_alive() for t in self._readers
                ):
                    raise Failed(f"{self.name} exited ({self.proc.returncode}) "
                                 f"before /{pattern}/\n{self.tail()}")
                left = end - time.monotonic()
                if left <= 0:
                    raise Failed(f"{self.name}: no /{pattern}/ in {timeout:.0f} s"
                                 f"\n{self.tail()}")
                self._cv.wait(min(left, 1.0))

    def command(self, line: str, timeout: float = 120.0) -> dict:
        """Write one command line; return the JSON object of its answer
        (the next stdout line that starts with ``{``)."""
        with self._cv:
            mark = len(self.lines)
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        _, _, m = self.wait_line(r"^(\{.*\})$", timeout, start=mark, stream="out")
        return json.loads(m.group(1))

    def tail(self, n: int = 40) -> str:
        with self._cv:
            return "\n".join(f"  {self.name}| {ln}" for _, _, ln in self.lines[-n:])

    def stop(self, sig=signal.SIGTERM, timeout: float = 60.0) -> Optional[int]:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for t in self._readers:
            t.join(5)
        return self.proc.returncode

    def dump(self, path: Path) -> None:
        with open(path, "w") as f:
            for t, tag, ln in self.lines:
                f.write(f"{t - self.t0:10.3f} {tag} {ln}\n")


def device_holders() -> dict:
    """pid -> cmdline of every process holding an accelerator device file."""
    held = {}
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if re.fullmatch(r"/dev/(accel\d+|vfio/\d+)", target):
            pid = fd.split("/")[2]
            try:
                cmd = Path(f"/proc/{pid}/cmdline").read_bytes()
            except OSError:
                continue
            held[int(pid)] = cmd.replace(b"\0", b" ").decode(errors="replace")
    return held


def wait_chip_free(timeout: float = 60.0) -> float:
    """Seconds until no process holds the chip; fails after ``timeout``."""
    t = time.monotonic()
    while True:
        held = device_holders()
        if not held:
            return time.monotonic() - t
        if time.monotonic() - t > timeout:
            raise Failed(f"chip still held {timeout:.0f} s later: {held}")
        time.sleep(0.05)


def child_env(extra: Optional[dict] = None) -> dict:
    """The environment of every child: the checkout first on the import
    path, no per-chunk miner log (it would swallow the miner's class
    prewarm), and whatever the caller adds."""
    env = dict(os.environ)
    env.pop("BMT_MINER_LOG", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(HERE)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.update(extra or {})
    return env
