"""Finds the knee of an open-loop cell, once, when the cell is defined.

    python benchmark/sweep.py --workload <name> --seed <n> --seconds <s> --rates 2,2.5,3.1,3.9

One server, warmed up at the highest rate, then one ramp + window + drain
at each rate in turn. The knee is the highest rate at which the requests
completed inside the window are at least 0.97 of the requests due in it and
``vllm:num_requests_waiting`` is no higher at the window's end than at its
start. The cell then runs at 0.8 of it (``benchmark/cells/<name>.json``).
Not part of a benchmark run: the driver never calls it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import run_cell as rc  # noqa: E402
from benchmark.harness import score as scoring  # noqa: E402
from benchmark.harness import traffic as traffic_mod  # noqa: E402
from benchmark.harness.client import Client  # noqa: E402
from benchmark.harness.manifest import ROOT, Cell, load_manifest  # noqa: E402
from benchmark.harness.server import Server  # noqa: E402


async def sweep(cell: Cell, server: Server, args, rates: list[float]) -> list[dict]:
    hf = cell.hf_config
    vocab, positions = hf["vocab_size"], hf["max_position_embeddings"]
    rows = []
    async with Client(server.port, str(server.model_dir)) as client:
        await rc.wait_ready(client, server, 1100.0)
        # The cell's walk of its ladder; a rate that runs past the ladder's
        # reach (its file says where that is) shows in "first uses".
        rc.log(f"warm-up: {await rc.warm_up(client, cell, vocab)}")
        for i, rate in enumerate(rates):
            ramp_s = float(cell.traffic["ramp_s"])
            plan = traffic_mod.build_plan(cell.traffic, rate, ramp_s, args.seconds,
                                          args.seed + i, vocab, positions)
            client.t0 = time.perf_counter() + ramp_s + 0.5
            scrapes: dict = {}
            side = [asyncio.create_task(rc.scrape_at(client, 0.0, scrapes, "m0")),
                    asyncio.create_task(rc.scrape_at(client, args.seconds, scrapes, "m1"))]
            recs = await client.run_plan(plan, float(cell.traffic["drain_limit_s"]))
            await asyncio.gather(*side)
            s = scoring.score(recs, args.seconds, cell.chips)
            m0, m1 = scrapes["m0"][1], scrapes["m1"][1]
            done_in = sum(1 for r in recs if r.ok and 0.0 <= r.chunks[-1] < args.seconds)
            delta = lambda *n: (rc.first(m1, *n) or 0.0) - (rc.first(m0, *n) or 0.0)
            row = {
                "rate": rate, "due": s["attempted"], "failed": s["failed"],
                "completed_in_window": done_in,
                "keeps_up": done_in / max(s["attempted"], 1),
                "waiting_start": rc.first(m0, "vllm:num_requests_waiting"),
                "waiting_end": rc.first(m1, "vllm:num_requests_waiting"),
                "running_end": rc.first(m1, "vllm:num_requests_running"),
                "kv_usage_end": rc.first(m1, "vllm:gpu_cache_usage_perc"),
                "preemptions": delta("vllm:num_preemptions_total", "vllm:num_preemptions"),
                "compiles": delta(*rc.COMPILES),
                "steps": delta(rc.STEP_COUNT),
                "drained_s": client.now() - args.seconds,
                **{k: round(v, 2) for k, v in s["metrics"].items()},
                "gen_late_p95_ms": s["facts"]["gen_late_p95_ms"],
            }
            rows.append(row)
            rc.log(f"sweep row: {json.dumps(row)}")
    return rows


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    cell = Cell(load_manifest(), args.workload)
    out_dir = ROOT / "bench_out" / args.workload / f"sweep-seed{args.seed}"
    out_dir.mkdir(parents=True, exist_ok=True)
    server = Server(cell, args.seed, out_dir, ROOT / ".jax_cache")
    server.start()
    try:
        rows = asyncio.run(sweep(cell, server, args, rates))
    except rc.RunFailed as e:
        rc.log(f"FAILED: {e}\n{server.log_tail()}")
        return 1
    finally:
        server.stop()
    ok = [r["rate"] for r in rows
          if r["keeps_up"] >= 0.97 and (r["waiting_end"] or 0) <= (r["waiting_start"] or 0)
          and not r["failed"]]
    print(json.dumps({"rows": rows, "knee": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
