"""One run of one cell of the benchmark.

    python benchmark/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

This process never touches JAX. It writes the configuration's model
directory (``config.json`` only), starts the OpenAI server as a child with
the README's ``serve`` entry point, drives it over HTTP from an asyncio
client, stops it, and then starts the checker (the float32 reference on the
chip) and, with ``--trace 1`` or ``2``, the trace reducer. The last line
printed is the result object of the benchmark's contract; everything else
worth keeping goes on earlier lines and into ``bench_out/<workload>/``.

``--trace 2`` measures first and traces afterwards, in one server: it is a
``--trace 0`` run up to the moment the window's plan has returned and the
two scrapes are in, and every end-to-end number comes from that clean
window. Only then does it touch the profiler: a second plan of the same
mix and rate (its own order of work from the traffic file, not the window's
replayed), and slices through ``/start_profile`` once its ramp is over
(``traced_segment``). ``--trace 1`` (slices INSIDE the window, nothing of
the run a measurement) stays for a tree without ``trace_in_run``.

Set-up, all of it counted in ``setup_s``: server start; a warm-up that
walks the cell's ladder of step programs, the same requests in every run;
the correctness requests; a ramp in which the cell's arrivals already run.
Then the window, in which no step program should be used for the first
time: every run logs how many were.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.harness import score as scoring  # noqa: E402
from benchmark.harness import traffic as traffic_mod  # noqa: E402
from benchmark.harness.client import Client  # noqa: E402
from benchmark.harness.manifest import (  # noqa: E402
    BENCH_DIR, ROOT, Cell, kernel_counts, load_manifest, peaks_for,
)
from benchmark.harness.server import (  # noqa: E402
    NO_CHIP, Server, child_env, end_sessions, session_members,
)

WARM_SEED = 7_000_003  # the warm-up's traffic never depends on --seed
COMPILES = ("vllm:step_bucket_compiles_total", "vllm:step_bucket_compiles")
STEP_COUNT = 'vllm:engine_step_duration_seconds_count{phase="dispatch"}'
# --trace 2: arrivals of the traced segment after its ramp, and for how much
# longer they and the profiler's attempts go on while no slice holds the
# device.
SEGMENT_S = 8.0
SEGMENT_MORE_S = 12.0


class RunFailed(Exception):
    pass


def log(msg: str) -> None:
    print(f"[run_cell {time.perf_counter() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


def first(m: dict, *names: str):
    for n in names:
        if n in m:
            return m[n]
    return None


async def wait_ready(client: Client, server: Server, limit_s: float) -> None:
    deadline = time.perf_counter() + limit_s
    while not await client.ready():
        code = server.exited()
        if code is not None:
            raise RunFailed(f"the server exited with code {code} before it was ready"
                            + (" (no TPU)" if code == NO_CHIP else ""))
        if time.perf_counter() > deadline:
            raise RunFailed(f"the server was not ready after {limit_s} s")
        await asyncio.sleep(0.25)


async def warm_up(client: Client, cell: Cell, vocab: int) -> dict:
    """Walk the cell's ladder of step programs: the waves of
    ``cells/<cell>.json``, one after the other, each sent at once and
    awaited, with the traffic's own sampling (the sampler's settings are
    static arguments of the step program). A wave is a list of
    ``[count, prompt tokens, answer tokens]``; what each is there to reach
    is said beside it in the file. The same requests in every run: nothing
    here depends on ``--seed``."""
    t_begin = time.perf_counter()
    before = first(await client.metrics(), *COMPILES) or 0.0
    new_by_wave = []
    for i, wave in enumerate(cell.numbers["warmup_waves"]):
        plan = traffic_mod.wave_plan(wave, cell.traffic["sampling"], WARM_SEED + i, vocab)
        client.t0 = time.perf_counter()
        recs = await client.run_plan(plan, drain_limit_s=900.0)  # a cold run compiles here
        bad = [r for r in recs if not r.ok]
        if bad:
            raise RunFailed(f"warm-up wave {i}: {len(bad)} of {len(recs)} requests failed: "
                            f"{bad[0].error or bad[0].status}")
        after = first(await client.metrics(), *COMPILES) or 0.0
        new_by_wave.append(after - before)
        before = after
    return {"waves": len(new_by_wave), "new_programs_by_wave": new_by_wave,
            "seconds": time.perf_counter() - t_begin, "programs_after": before}


async def check_served(client: Client, cell: Cell, seed: int, vocab: int, positions: int) -> list[dict]:
    reqs, together = traffic_mod.check_requests(cell.traffic, seed, vocab, positions)
    if together:
        outs = await asyncio.gather(*(client.complete(q) for q in reqs))
    else:
        outs = [await client.complete(q) for q in reqs]
    samples = []
    for q, out in zip(reqs, outs):
        lp = out["choices"][0]["logprobs"]
        tokens = [int(t) for t in lp["tokens"]]
        if len(tokens) != q.max_tokens:
            raise RunFailed(f"check request: asked {q.max_tokens} tokens, got {len(tokens)}")
        samples.append({"prompt": q.prompt, "tokens": tokens,
                        "top_logprobs": [{str(k): v for k, v in top.items()}
                                         for top in lp["top_logprobs"]]})
    return samples


def has_device_plane(trace_dir: Path) -> bool:
    """Whether the profiler's file holds a device plane with operations, by
    the names the file stores as plain strings (no JAX in this process)."""
    for f in trace_dir.rglob("*.xplane.pb"):
        data = f.read_bytes()
        return b"/device:TPU:" in data and b"XLA Ops" in data
    return False


async def traced(client: Client, at_s: float, window_s: float, until_s: float, for_s: float,
                 every_s: float, most: int, trace_root: Path, info: dict) -> None:
    """Profile ``for_s`` seconds through ``/start_profile``, again every
    ``every_s`` seconds, and keep each slice that holds the device (on the
    v5e most traces started from the HTTP thread of a busy server come back
    without a device plane; PERF.md, PR 25): at most ``most`` slices, spread
    over the window. Past the window's end only for as long as there is none
    at all, and never past ``until_s``."""
    await asyncio.sleep(max(0.0, at_s - client.now()))
    slices, profiled, n = [], [], 0
    while len(slices) < most and client.now() < (until_s if not slices else window_s - for_s):
        began = client.now()
        d = trace_root / f"attempt{n}"
        n += 1
        await client.post_json("/start_profile", {"trace_dir": str(d)})
        lo = client.now()
        await asyncio.sleep(for_s)
        hi = client.now()
        await client.post_json("/stop_profile")
        profiled.append((began, client.now()))
        if has_device_plane(d):
            slices.append({"lo": lo, "hi": hi, "dir": str(d), "stopped": client.now()})
        else:
            shutil.rmtree(d, ignore_errors=True)
        if len(slices) < most:
            await asyncio.sleep(max(0.5, began + every_s - client.now()))
    info.update(attempts=n, slices=slices, profiled=profiled)
    client.enough.set()


async def profiled_slice(client: Client, d: Path, for_s: float) -> dict:
    """One slice through the server's profiler control, with a ``/metrics``
    scrape just AFTER the start has returned and one just BEFORE the stop is
    sent: a counter's delta covers no more than the trace does, so a
    roofline share read from the two is under-read, never over-read."""
    await client.post_json("/start_profile", {"trace_dir": str(d)})
    lo, m_lo = client.now(), await client.metrics()
    await asyncio.sleep(for_s)
    m_hi, hi = await client.metrics(), client.now()
    stop = await client.post_json("/stop_profile")
    return {"lo": lo, "hi": hi, "dir": str(d), "m_lo": m_lo, "m_hi": m_hi,
            "stop_s": stop.get("stop_s")}


async def traced_segment(client: Client, plan, for_s: float, most: int,
                         trace_root: Path) -> dict:
    """``--trace 2``, after the window has closed: drive ``plan`` (the same
    mix and rate as segment 1, its own ramp, then ``SEGMENT_S``) and
    take profiler slices once the ramp is over. The first start and stop of
    the profiler in a process cost more than the others, so one throw-away
    pair goes first, inside the ramp. Up to ``most`` slices that hold the
    device are kept; while there is none the attempts go on, for at most
    ``SEGMENT_MORE_S`` more. Then whatever of the plan is still running is
    dropped: nothing here is scored."""
    shutil.rmtree(trace_root, ignore_errors=True)
    client.enough.clear()
    plan.score_until = plan.start_s - 1.0  # no request of it has to finish
    client.t0 = time.perf_counter() - plan.start_s + 0.5
    info: dict = {"attempts": 0, "slices": [], "profiled": []}

    async def slices() -> None:
        try:
            first = await profiled_slice(client, trace_root / "first", 0.2)
            info["first_stop_s"] = first["stop_s"]
            shutil.rmtree(trace_root / "first", ignore_errors=True)
            await asyncio.sleep(max(0.0, -client.now()))
            kept = info["slices"]
            while len(kept) < most and client.now() < (
                    SEGMENT_S + (0.0 if kept else SEGMENT_MORE_S) - for_s):
                d = trace_root / f"attempt{info['attempts']}"
                info["attempts"] += 1
                sl = await profiled_slice(client, d, for_s)
                if has_device_plane(d):
                    kept.append(sl)
                else:
                    shutil.rmtree(d, ignore_errors=True)
        finally:
            client.enough.set()

    side = asyncio.create_task(slices())
    info["records"] = await client.run_plan(plan, 0.0)
    await side
    return info


async def scrape_at(client: Client, at_s: float, into: dict, key: str) -> None:
    await asyncio.sleep(max(0.0, at_s - client.now()))
    into[key] = (client.now(), await client.metrics())


async def drive(cell: Cell, server: Server, args, out_dir: Path) -> dict:
    hf = cell.hf_config
    vocab, positions = hf["vocab_size"], hf["max_position_embeddings"]
    rate = args.rate if args.rate is not None else cell.numbers.get("rate_per_s", 0.0)
    res: dict = {}
    async with Client(server.port, str(server.model_dir)) as client:
        await wait_ready(client, server, 1100.0)
        res["ready_s"] = time.perf_counter() - T_PROCESS_START
        log(f"server ready after {res['ready_s']:.1f} s")
        if not args.check_only:
            res["warmup"] = await warm_up(client, cell, vocab)
            log(f"warm-up: {res['warmup']}")
        res["samples"] = await check_served(client, cell, args.seed, vocab, positions)
        log(f"check requests served: {len(res['samples'])}")
        if args.check_only:
            return res

        ramp_s = float(cell.traffic["ramp_s"])
        plan = traffic_mod.build_plan(cell.traffic, rate, ramp_s, args.seconds,
                                      args.seed, vocab, positions)
        client.t0 = time.perf_counter() + ramp_s + 0.5
        res["setup_s"] = client.t0 - T_PROCESS_START
        scrapes: dict = {}
        trace_info: dict = {}
        side = [asyncio.create_task(scrape_at(client, 0.0, scrapes, "m0")),
                asyncio.create_task(scrape_at(client, args.seconds, scrapes, "m1"))]
        if args.trace == 1:
            # The same mix goes on after the window, unscored, for as long
            # as the profiler needs to give a device trace.
            more = traffic_mod.build_plan(cell.traffic, rate, 0.0, args.trace_extension,
                                          args.seed, vocab, positions, segment=1)
            plan = traffic_mod.extend(plan, more, args.trace_extension)
            shutil.rmtree(out_dir / "trace", ignore_errors=True)
            side.append(asyncio.create_task(traced(
                client, 0.1 * args.seconds, args.seconds,
                args.seconds + args.trace_extension - 5.0, args.trace_seconds,
                args.seconds / (args.trace_slices + 1.0), args.trace_slices,
                out_dir / "trace", trace_info)))
        else:
            client.enough.set()
        recs = await client.run_plan(plan, float(cell.traffic["drain_limit_s"]))
        await asyncio.gather(*side)
        res.update(records=recs, scrapes=scrapes, trace_info=trace_info,
                   drained_s=client.now() - args.seconds,
                   waiting_end=first(scrapes["m1"][1], "vllm:num_requests_waiting"),
                   waiting_start=first(scrapes["m0"][1], "vllm:num_requests_waiting"))
        if args.trace == 2:
            # The window is closed and everything scored is in `res`. The
            # plan was NOT extended: itl_p95_ms pools the gaps of the drain
            # too, and a drain under continued arrivals reads differently.
            more = traffic_mod.build_plan(cell.traffic, rate, ramp_s,
                                          SEGMENT_S + SEGMENT_MORE_S,
                                          args.seed, vocab, positions, segment=1)
            res["trace_info"] = await traced_segment(
                client, more, args.trace_seconds, args.trace_slices,
                out_dir / "trace")
    return res


def run_child(cmd: list[str], env: dict, log_path: Path, limit_s: float,
              sessions: list[int]) -> int:
    """Run the checker or the reducer in a session of its own, noted in
    ``sessions``. Whether it returns or is cut at ``limit_s``, its whole
    group is ended with it: a child that is killed alone leaves whatever
    it started behind, and the driver refuses a run that does. The child is
    reaped only AFTER its session is ended, so the pid the group is killed
    by is still its own."""
    with open(log_path, "w") as f:
        proc = subprocess.Popen(cmd, cwd=str(ROOT), env=env, stdout=f,
                                stderr=subprocess.STDOUT, start_new_session=True)
        sessions.append(proc.pid)
        deadline = time.monotonic() + limit_s
        seen = os.WEXITED | os.WNOHANG | os.WNOWAIT  # look, do not reap
        while os.waitid(os.P_PID, proc.pid, seen) is None and time.monotonic() < deadline:
            time.sleep(0.05)
        end_sessions([proc.pid])
        return proc.wait()  # its own exit code, or -9 where it was cut


def compared(key: str, value: float, limit: float) -> str:
    return f"correct: {key} = {value:.6g} (limit {limit:g}) {'ok' if value <= limit else 'OVER'}"


def decide_correct(cell: Cell, check: dict) -> tuple[bool, dict]:
    """(``correct``, each number compared beside its limit): the limits are
    the cell's (``benchmark/cells/``), the numbers the checker's."""
    checked = {key: {"value": check[key], "limit": limit}
               for key, limit in cell.numbers["correct_limits"].items()}
    return all(c["value"] <= c["limit"] for c in checked.values()), checked


def layer_context(cell: Cell, res: dict, scored: dict, trace: dict | None,
                  device_kind: str, seconds: float) -> dict:
    (t0, m0), (t1, m1) = res["scrapes"]["m0"], res["scrapes"]["m1"]

    def delta(*names: str):
        for n in names:
            if n in m0 and n in m1:
                return m1[n] - m0[n]
        return None

    def steps():
        return delta(STEP_COUNT)

    slices = res["trace_info"].get("slices", [])
    # --trace 2 traces a segment of its own, after the window: the slices'
    # times are on that segment's clock, as are these records.
    traced_records = res["trace_info"].get("records", res["records"])

    def decode_ctx():
        # (decode tokens, sum of their context lengths) in the traced
        # slices. Tokens computed just before a slice began arrive inside
        # it and those computed at its end arrive after it; 0.15 s are
        # dropped at each start so that the count stays on the low side.
        parts = [scoring.decode_context_tokens(traced_records, sl["lo"] + 0.15, sl["hi"])
                 for sl in slices]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)

    def slice_delta(*names: str):
        """A counter's deltas between the two scrapes inside each kept
        slice, summed; None where the slices carry no scrapes (--trace 1)
        or the counter is missing."""
        for n in names:
            if slices and all(n in sl.get(k, ()) for sl in slices for k in ("m_lo", "m_hi")):
                return sum(sl["m_hi"][n] - sl["m_lo"][n] for sl in slices)
        return None

    return {
        "cell": cell, "config": cell.hf_config, "facts": scored["facts"],
        "service": scored["metrics"],
        "m0": m0, "m1": m1, "scrape_dt": t1 - t0, "delta": delta, "steps": steps,
        "records": res["records"], "seconds": seconds, "trace": trace,
        "trace_slices": [(sl["lo"], sl["hi"]) for sl in slices],
        # Every attempt, kept or not, from its start to the return of
        # /stop_profile, which stalls the server for seconds.
        "profiled": res["trace_info"].get("profiled", []),
        "decode_context_tokens": decode_ctx, "slice_delta": slice_delta,
        "kernel_counts": kernel_counts,
        "peaks": peaks_for(device_kind),
    }


def main(argv: list[str]) -> int:
    """One run. The result line is printed only once no process of the
    sessions the run started (server, checker, reducer) is left: whatever is
    found there at the end is killed and waited for, and only a process that
    outlives that too (30 s under SIGKILL: stuck in the kernel) fails the run,
    without a line."""
    sessions: list[int] = []
    left: list = []
    try:
        code, result = run(argv, sessions)
    finally:
        found = session_members(sessions)
        if found:
            log(f"{len(found)} process(es) of this run were still there at its end and "
                f"are killed: {found}")
            left = end_sessions(sessions, 30.0)
    if left:
        log(f"FAILED: {len(left)} process(es) of this run outlived it: {left}")
        return 1
    if result is not None:
        # Each number compared beside its limit, printed ONCE: the last lines
        # on standard error; the line on standard output has them as its
        # last key.
        for key, c in result["checked"].items():
            print(compared(key, **c), file=sys.stderr)
        print(json.dumps(result))
    return code


def run(argv: list[str], sessions: list[int]) -> tuple[int, dict | None]:
    """The run itself: (exit code, the object of the last line or None)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    # For the builder of a cell, not for the driver:
    ap.add_argument("--rate", type=float, default=None, help="offered rate, for the sweep")
    ap.add_argument("--engine-arg", action="append", default=[],
                    help="key=value: an engine argument for a control run (fp8 KV, int8 weights)")
    ap.add_argument("--check-only", action="store_true",
                    help="serve the check requests and compare; no warm-up, no window")
    ap.add_argument("--trace-seconds", type=float, default=1.5)
    ap.add_argument("--trace-slices", type=int, default=3,
                    help="how many slices of --trace-seconds: with --trace 1 spread over the "
                         "window, with --trace 2 taken after it")
    ap.add_argument("--trace-extension", type=float, default=60.0,
                    help="with --trace 1: how long the traffic may go on after the window")
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell = Cell(manifest, args.workload)
    extra = {}
    for kv in args.engine_arg:
        k, _, v = kv.partition("=")
        extra[k] = {"true": True, "false": False}.get(v.lower(), v)

    tag = f"seed{args.seed}-trace{args.trace}" + ("-control" if extra else "")
    out_dir = ROOT / "bench_out" / args.workload / tag
    out_dir.mkdir(parents=True, exist_ok=True)
    cache_dir = ROOT / ".jax_cache"
    server = Server(cell, args.seed, out_dir, cache_dir, extra)
    log(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    server.start()
    sessions.append(server.proc.pid)
    facts = None
    try:
        res = asyncio.run(drive(cell, server, args, out_dir))
    except RunFailed as e:
        log(f"FAILED: {e}\n--- server log ---\n{server.log_tail()}")
        return 1, None
    except Exception:
        log(f"--- server log ---\n{server.log_tail()}")
        raise
    finally:
        facts = server.stop()
    if not facts:
        log(f"FAILED: the server left no device facts\n{server.log_tail()}")
        return 1, None
    if facts["platform"] != "tpu":
        log(f"FAILED: the server ran on {facts}")
        return 1, None
    log(f"server stopped; device {facts}")

    # The reference, on the chip the server has just freed.
    env = child_env(cache_dir)
    job = {"config": cell.hf_config, "family": cell.family, "seed": args.seed,
           "chips": cell.chips, "samples": res["samples"],
           "dtype": {**cell.engine_args, **extra}.get("dtype", "bfloat16"),
           # A limit on a `_factor` asks for the reference's second pass.
           "factors": any(k.endswith("_factor") for k in cell.numbers["correct_limits"])}
    (out_dir / "check_job.json").write_text(json.dumps(job))
    rc = run_child([sys.executable, str(BENCH_DIR / "harness" / "checker.py"),
                    str(out_dir / "check_job.json"), str(out_dir / "check.json")],
                   env, out_dir / "checker.log", 600.0, sessions)
    if rc != 0:
        log(f"FAILED: the checker exited with {rc}\n{(out_dir / 'checker.log').read_text()[-3000:]}")
        return 1, None
    check = json.loads((out_dir / "check.json").read_text())
    correct, checked = decide_correct(cell, check)
    log(f"check: {json.dumps({k: v for k, v in check.items() if k != 'per_sample'})}")
    # A sequence apart: worst_sequence_mean_abs is the largest of these means.
    log("check, by sequence (prompt tokens, mean_abs, max_abs): "
        + json.dumps([[p["prompt"], p["mean_abs"], p["max_abs"]] for p in check["per_sample"]]))
    if args.check_only:
        return 0, {"correct": correct, "check": check, "device": facts, "checked": checked}

    scored = scoring.score(res["records"], args.seconds, cell.chips)
    (out_dir / "requests.jsonl").write_text("".join(json.dumps({
        "due": r.due, "sent": r.sent, "prompt_tokens": r.prompt_tokens,
        "max_tokens": r.max_tokens, "first": r.chunks[0] if r.chunks else None,
        "last": r.chunks[-1] if r.chunks else None, "failed": scoring.failed(r),
    }) + "\n" for r in res["records"] if r.scored))
    log(f"facts: {json.dumps(scored['facts'])}")
    log(f"drained {res['drained_s']:.1f} s after the window; requests waiting at its "
        f"start {res['waiting_start']} and end {res['waiting_end']}")
    seen = [first(m, *COMPILES) or 0.0 for m in
            (res["scrapes"]["m0"][1], res["scrapes"]["m1"][1])]
    log(f"first uses of a step program: {res['warmup']['programs_after']:.0f} in the warm-up, "
        f"{seen[0] - res['warmup']['programs_after']:.0f} in the check and the ramp, "
        f"{seen[1] - seen[0]:.0f} IN THE WINDOW"
        + (" -- each stalls the running requests: this window is not steady"
           if seen[1] > seen[0] else ""))
    e2e = dict(scored["metrics"], setup_s=res["setup_s"])
    log(f"end to end: {json.dumps(e2e)}")
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    device = dict(facts)
    result: dict = {"correct": correct, "attempted": scored["attempted"],
                    "failed": scored["failed"]}
    # The end-to-end metrics: all a --trace 0 line holds, and under
    # --trace 2 (clean window first) side by side with the per-layer ones.
    measured = {
        m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
        for m in cell.end_to_end if m["name"] in e2e
    }

    if args.trace:
        slices = res["trace_info"].get("slices", [])
        log(f"profiler: {res['trace_info'].get('attempts')} attempt(s), slices with the device: "
            f"{[(round(sl['lo'], 2), round(sl['hi'], 2)) for sl in slices]}; from an attempt's "
            f"start to the return of its /stop_profile: "
            f"{[(round(lo, 2), round(hi, 2)) for lo, hi in res['trace_info'].get('profiled', [])]}"
            + (f"; /stop_profile took {[sl.get('stop_s') for sl in slices]} s, the throw-away "
               f"first {res['trace_info'].get('first_stop_s')} s" if args.trace == 2 else ""))
        if not slices:
            log("FAILED: no trace with a device plane")
            return 1, None
        env_cpu = dict(env, JAX_PLATFORMS="cpu")
        rc = run_child([sys.executable, str(BENCH_DIR / "harness" / "trace_reduce.py"),
                        str(out_dir / "trace.json"), *(sl["dir"] for sl in slices)],
                       env_cpu, out_dir / "trace_reduce.log", 300.0, sessions)
        if rc != 0:
            log(f"FAILED: trace reduction exited with {rc}\n"
                f"{(out_dir / 'trace_reduce.log').read_text()[-3000:]}")
            return 1, None
        trace = json.loads((out_dir / "trace.json").read_text())
        if args.trace == 2:
            shutil.rmtree(out_dir / "trace", ignore_errors=True)  # reduced: not kept
        ctx = layer_context(cell, res, scored, trace, facts["kind"], args.seconds)
        values = {}
        for m in cell.per_layer:
            v = cell.layer_metric(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        traced_steps = ctx["slice_delta"](STEP_COUNT)
        if traced_steps:
            # What tracing costs while it is ON, for PERF.md.
            log(f"step interval inside the traced slices: "
                f"{1e3 * sum(sl['hi'] - sl['lo'] for sl in slices) / traced_steps:.3f} ms over "
                f"{traced_steps:.0f} steps; in the clean window "
                f"{1e3 * ctx['scrape_dt'] / (ctx['steps']() or float('nan')):.3f} ms")
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
        if args.trace == 2:
            result["metrics"] = {**measured, **values}
        else:
            result["metrics"] = values
            log(f"end to end in this TRACED run (not a measurement): {json.dumps(e2e)}")
    else:
        result["metrics"] = measured
    result["device"] = device
    result["checked"] = checked
    (out_dir / "result.json").write_text(json.dumps(result))
    return 0, result


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
