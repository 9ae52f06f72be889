"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
by the names the trace prints, and the longest idle gaps.

The benchmark's own reduction (``vllm_tpu/metrics/op_split.py`` classifies
by op names this JAX no longer prints). It reads the file with
``jax.profiler.ProfileData`` and nothing else of JAX, in a process that is
told to stay on the CPU backend.

What the trace of this JAX holds, as read on the v5e: one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per launch
of a jitted program), ``XLA Ops`` (one event per HLO operation; an
operation inside a ``while`` or a fusion's parent nests inside its
parent's event) and ``Steps``. Host threads are lines of the plane
``/host:CPU``.

- busy: the union of the ``XLA Ops`` events' intervals, per chip.
- window: first start to last end of those events, per chip. Idle at the
  two edges of the trace is therefore not seen (less than one step).
- self time of an operation: its duration minus its children's, so that a
  ``while`` is not counted twice. ``kernel_seconds`` sums self time by the
  operation's name with its trailing ``.<number>`` removed.
- idle gaps: the complement of busy inside the window, each labelled with
  the innermost host-thread event (of at least 50 us) that covers half of
  it or more, or ``unattributed`` where no host event overlaps it.

Run as ``python trace_reduce.py <out.json> <trace dir or file>...``: several
traces (the slices of one run) are reduced one by one and added up, so that
busy, window and kernel time are those of their union.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"(\.\d+)+$")


def kernel_name(op_name: str) -> str:
    """The name the trace prints for an operation, without its instance
    number. On the TPU an ``XLA Ops`` event is named by the whole HLO
    instruction, ``%fusion.123 = bf16[...] fusion(...)``: the name is what
    stands before `` = ``. ``fusion.123`` -> ``fusion``."""
    head = op_name.strip().split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals and the gaps
    between its parts, all in the intervals' unit."""
    busy = 0.0
    gaps: list[tuple[float, float]] = []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def self_times(events: list[tuple[float, float, str]]) -> dict[str, list[float]]:
    """``name -> [self seconds, calls]`` from ``(start, end, name)`` events of one
    line, in which a child's interval lies inside its parent's."""
    out: dict[str, list[float]] = {}
    stack: list[list] = []  # [end, name, child_time, start]

    def close(item) -> None:
        end, name, child, start = item
        rec = out.setdefault(name, [0.0, 0])
        rec[0] += max(0.0, (end - start) - child)
        rec[1] += 1

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def reduce_planes(planes: list[dict], top: int = 10) -> dict:
    """``planes``: ``[{"name", "lines": [{"name", "events": [(start_ns,
    dur_ns, name)]}]}]``. Split from the file reading so that a test can
    hand it a trace it wrote down by hand."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError(
            "the trace has no plane named " + DEVICE_PREFIX + "*; it has "
            + ", ".join(p["name"] for p in planes))
    busy = window = 0.0
    kernel: dict[str, list[float]] = {}
    all_gaps: list[tuple[float, float]] = []
    modules: dict[str, list[float]] = {}
    for p in devices:
        ops = [ev for l in p["lines"] if l["name"] == OPS_LINE for ev in l["events"]]
        if not ops:
            continue
        iv = [(s, s + d) for s, d, _ in ops]
        b, gaps = union_seconds(iv)
        busy += b * 1e-9
        window += (max(e for _, e in iv) - min(s for s, _ in iv)) * 1e-9
        all_gaps += gaps
        for name, (sec, calls) in self_times(
                [(s, s + d, kernel_name(n)) for s, d, n in ops]).items():
            rec = kernel.setdefault(name, [0.0, 0])
            rec[0] += sec * 1e-9
            rec[1] += calls
        for l in p["lines"]:
            if l["name"] == MODULES_LINE:
                for s, d, n in l["events"]:
                    rec = modules.setdefault(kernel_name(n.split("(")[0]), [0.0, 0])
                    rec[0] += d * 1e-9
                    rec[1] += 1
    n = max(len(devices), 1)
    host = [l for p in planes if p["name"].startswith(HOST_PLANE) for l in p["lines"]]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "devices": len(devices),
        "busy_s": busy / n,
        "window_s": window / n,
        "kernel_seconds": {k: v[0] / n for k, v in kernel.items()},
        "kernel_calls": {k: v[1] for k, v in kernel.items()},
        "module_seconds": {k: v[0] / n for k, v in modules.items()},
        "module_calls": {k: v[1] for k, v in modules.items()},
        "idle_gaps": [[label_gap(g, host), (g[1] - g[0]) * 1e-9] for g in longest],
        "device_ops": sorted(
            ([k, v[0] / n] for k, v in kernel.items()), key=lambda x: -x[1])[:top],
    }


def combine(parts: list[dict], top: int = 10) -> dict:
    """The slices of one run as one trace: times and calls add up, the
    longest gaps and the heaviest operations are those of all slices."""
    def added(key: str) -> dict:
        out: dict = {}
        for p in parts:
            for k, v in p[key].items():
                out[k] = out.get(k, 0) + v
        return out

    kernel_seconds = added("kernel_seconds")
    return {
        "devices": parts[0]["devices"],
        "slices": len(parts),
        "busy_s": sum(p["busy_s"] for p in parts),
        "window_s": sum(p["window_s"] for p in parts),
        "kernel_seconds": kernel_seconds,
        "kernel_calls": added("kernel_calls"),
        "module_seconds": added("module_seconds"),
        "module_calls": added("module_calls"),
        "idle_gaps": sorted((g for p in parts for g in p["idle_gaps"]),
                            key=lambda g: -g[1])[:top],
        "device_ops": sorted(([k, v] for k, v in kernel_seconds.items()),
                             key=lambda x: -x[1])[:top],
    }


def label_gap(gap: tuple[float, float], host_lines: list[dict]) -> str:
    """What the host was doing in the gap, as ``thread: event``: the
    shortest host event (so the innermost frame) that covers at least half
    of the gap; failing that, the one that covers most of it."""
    lo, hi = gap
    half = 0.5 * (hi - lo)
    inner, inner_len = None, float("inf")
    most, most_cover = None, 0.0
    for l in host_lines:
        thread = l["name"].split("/")[0]
        for s, d, name in l["events"]:
            cover = min(hi, s + d) - max(lo, s)
            if cover <= 0:
                continue
            if cover >= half and d < inner_len:
                inner, inner_len = f"{thread}: {name}", d
            if cover > most_cover:
                most, most_cover = f"{thread}: {name}", cover
    return (inner or most or "unattributed")[:120]


def read_planes(path: str, min_host_ns: float = 50_000.0) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_trace(path))
    planes = []
    for p in data.planes:
        is_dev = p.name.startswith(DEVICE_PREFIX)
        if not is_dev and not p.name.startswith(HOST_PLANE):
            continue
        lines = []
        for l in p.lines:
            if is_dev and l.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [
                (e.start_ns, e.duration_ns, e.name) for e in l.events
                if is_dev or e.duration_ns >= min_host_ns
            ]
            lines.append({"name": l.name, "events": evs})
        planes.append({"name": p.name, "lines": lines})
    return planes


def describe(path: str) -> str:
    """What is in a trace, for a person: planes, lines, counts, a few
    events with their stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_trace(path))
    out = []
    for p in data.planes:
        lines = list(p.lines)
        out.append(f"PLANE {p.name!r}: {len(lines)} lines")
        for l in lines:
            evs = list(l.events)
            out.append(f"  LINE {l.name!r}: {len(evs)} events")
            if p.name.startswith(DEVICE_PREFIX):
                for e in evs[:40]:
                    out.append(f"     {e.name[:160]!r} start {e.start_ns} dur {e.duration_ns} "
                               f"stats {list(e.stats)[:8]}")
    return "\n".join(out)


def main(argv: list[str]) -> int:
    if argv[0] == "--describe":
        print(describe(argv[1]))
        return 0
    dst, *sources = argv
    with open(dst, "w") as f:
        json.dump(combine([reduce_planes(read_planes(src)) for src in sources]), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
