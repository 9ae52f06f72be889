"""Starts and stops the server child. The parent never touches JAX."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
from pathlib import Path

from .manifest import ROOT, Cell

NO_CHIP = 3


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
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
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
