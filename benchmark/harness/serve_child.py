"""The server process of a run: the README's ``serve`` command, called
through the CLI's own ``main``, in a process that holds the chip alone.

The only things added around it: it refuses to start where JAX finds no
TPU (or fewer chips than the cell asks for), so that nothing falls back to
the CPU, and when the server has drained and returned it writes what JAX
says of the device, with the peak of device memory, to the facts file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

NO_CHIP = 3


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks),
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--facts", required=True)
    ap.add_argument("--chips", type=int, required=True)
    args, serve_args = ap.parse_known_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, root)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"serve_child: need {args.chips} TPU chip(s), JAX has {devs}", flush=True)
        return NO_CHIP

    from vllm_tpu.entrypoints.cli.main import main as cli_main

    try:
        cli_main(["serve", *serve_args])
    finally:
        with open(args.facts + ".tmp", "w") as f:
            json.dump(device_facts(), f)
        os.replace(args.facts + ".tmp", args.facts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
