"""Starts and stops the server child. The parent never touches JAX."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

from .manifest import ROOT, Cell

NO_CHIP = 3


def session_members(sessions: list[int]) -> list[tuple[int, str]]:
    """(pid, command line) of every process alive in one of ``sessions``,
    from ``/proc``. Every child of a run is started with
    ``start_new_session=True``, so its session id is its pid and whatever
    it starts stays in that session unless it leaves on purpose."""
    out = []
    for d in Path("/proc").iterdir():
        if not d.name.isdigit():
            continue
        try:
            stat = (d / "stat").read_text()
            # "pid (comm) state ppid pgrp session ...": comm may hold anything.
            state, _ppid, _pgrp, session = stat[stat.rindex(")") + 2:].split()[:4]
            if state == "Z" or int(session) not in sessions:
                continue
            cmd = (d / "cmdline").read_bytes().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue  # gone between the listing and the read
        out.append((int(d.name), cmd.strip()))
    return out


def end_sessions(sessions: list[int], wait_s: float = 5.0) -> list[tuple[int, str]]:
    """SIGKILL to each session's process group and to whatever else is
    still in the session, then wait until none is left. Returns who is
    there all the same after ``wait_s`` (a process stuck in the kernel)."""
    deadline = time.monotonic() + wait_s
    while True:
        for sid in sessions:
            try:
                os.killpg(sid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        left = session_members(sessions)
        for pid, _ in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        if not left or time.monotonic() > deadline:
            return left
        time.sleep(0.05)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(cache_dir: Path) -> dict:
    """The child's environment: the caller's, with JAX's persistent
    compilation cache at one fixed place inside the checkout, so that only
    the first run of a cell in a checkout compiles. The program takes the
    directory from ``JAX_COMPILATION_CACHE_DIR`` and sets none in code."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # No size cap on it: where the machine sets one (this one does), the
    # server's and the checker's entries evict each other and every start
    # compiles everything again (PERF.md, PR 25).
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def engine_cli_args(engine_args: dict) -> list[str]:
    out: list[str] = []
    for key, val in engine_args.items():
        flag = key.replace("_", "-")
        if isinstance(val, bool):
            out.append(f"--{flag}" if val else f"--no-{flag}")
        else:
            out += [f"--{flag}", str(val)]
    return out


def write_model_dir(cell: Cell, work: Path) -> Path:
    """A checkpoint directory that holds only ``config.json``: the weights
    are made on the device from the seed (``load_format=dummy``)."""
    path = work / "model" / cell.config_name
    path.mkdir(parents=True, exist_ok=True)
    with open(path / "config.json", "w") as f:
        json.dump(cell.hf_config, f, indent=1)
    return path


class Server:
    def __init__(self, cell: Cell, seed: int, work: Path, cache_dir: Path,
                 extra_engine_args: dict | None = None) -> None:
        self.work = work
        self.model_dir = write_model_dir(cell, work)
        self.port = free_port()
        self.facts_path = work / "device_facts.json"
        self.facts_path.unlink(missing_ok=True)
        self.log_path = work / "server.log"
        engine_args = {**cell.engine_args, **(extra_engine_args or {})}
        self.cmd = [
            sys.executable, str(Path(__file__).with_name("serve_child.py")),
            "--facts", str(self.facts_path), "--chips", str(cell.chips),
            str(self.model_dir), "--host", "127.0.0.1", "--port", str(self.port),
            "--load-format", "dummy", "--seed", str(seed % 2**32),
            *engine_cli_args(engine_args),
        ]
        self.env = child_env(cache_dir)
        self.proc: subprocess.Popen | None = None

    def start(self) -> None:
        # To a file, never to a pipe nobody reads: a full pipe blocks the
        # server in write().
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            self.cmd, cwd=str(ROOT), env=self.env, stdout=self.log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def exited(self) -> int | None:
        return self.proc.poll()

    def stop(self, timeout: float = 120.0) -> dict | None:
        """SIGTERM (the server drains and returns), wait, then make sure
        nothing of its session is left. Returns the device facts the child
        wrote on its way out, or None."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                pass
        end_sessions([self.proc.pid])
        self.proc.wait()
        self.log.close()
        if self.facts_path.is_file():
            with open(self.facts_path) as f:
                return json.load(f)
        return None

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""
