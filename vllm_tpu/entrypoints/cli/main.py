"""`vllm-tpu` CLI: serve / complete / bench.

Reference analog: ``vllm/entrypoints/cli/main.py`` (`vllm serve/chat/
complete/bench`, serve.py:37 ServeSubcommand).
"""

from __future__ import annotations

import argparse
import sys

from vllm_tpu.engine.arg_utils import AsyncEngineArgs, EngineArgs


def _add_serve(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("serve", help="Start the OpenAI-compatible server")
    p.add_argument("model_tag", nargs="?", help="model name or path")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--tool-call-parser", default=None,
                   help="tool-call output parser (hermes/json/...)")
    p.add_argument("--reasoning-parser", default=None,
                   help="reasoning splitter (deepseek_r1/qwen3/think)")
    AsyncEngineArgs.add_cli_args(p)
    p.set_defaults(func=_run_serve)


def _run_serve(args: argparse.Namespace) -> None:
    from vllm_tpu.entrypoints.openai.api_server import run_server

    engine_args = AsyncEngineArgs.from_cli_args(args)
    if args.model_tag:
        engine_args.model = args.model_tag
    run_server(
        engine_args, host=args.host, port=args.port,
        tool_parser=args.tool_call_parser,
        reasoning_parser=args.reasoning_parser,
    )


def _add_complete(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("complete", help="One-shot offline completion")
    p.add_argument("model_tag", nargs="?")
    p.add_argument("--prompt", required=True)
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    EngineArgs.add_cli_args(p)
    p.set_defaults(func=_run_complete)


def _run_complete(args: argparse.Namespace) -> None:
    from vllm_tpu.entrypoints.llm import LLM
    from vllm_tpu.sampling_params import SamplingParams

    engine_args = EngineArgs.from_cli_args(args)
    if args.model_tag:
        engine_args.model = args.model_tag
    llm = LLM.from_engine_args(engine_args)
    outs = llm.generate(
        [args.prompt],
        SamplingParams(temperature=args.temperature, max_tokens=args.max_tokens),
    )
    print(outs[0].outputs[0].text)


def _add_bench(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "bench", help="Benchmarks (latency/throughput/serve/sessions/trace)")
    p.add_argument("mode",
                   choices=["latency", "throughput", "serve", "sessions",
                            "trace"])
    p.add_argument("--json", dest="json_out", default=None)
    EngineArgs.add_cli_args(p)
    p.add_argument("--num-prompts", type=int, default=100)
    p.add_argument("--input-len", type=int, default=32)
    p.add_argument("--output-len", type=int, default=128)
    p.add_argument(
        "--dataset", choices=["random", "sharegpt", "synthetic-conv"],
        default="random",
        help="workload: fixed-length random ids, a ShareGPT-format JSON "
             "(--dataset-path), or the conversation-shaped synthetic "
             "distribution (shared prefixes + lognormal lengths)",
    )
    p.add_argument("--dataset-path", default=None)
    # Dataset sampling reuses the engine --seed (fixed-seed protocol).
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--qps", type=float, default=0.0, help="serve mode request rate (0=inf)")
    p.add_argument(
        "--qps-sweep", default=None,
        help='serve mode QPS grid, e.g. "1,4,16,0" (0=inf); one engine, '
             "one combined result (the reference's bench serve sweep)",
    )
    p.add_argument(
        "--sessions", type=int, default=8,
        help="sessions mode: concurrent multi-turn chats",
    )
    p.add_argument(
        "--turns-per-session", type=int, default=4,
        help="sessions mode: turns per chat (each turn re-sends the "
             "growing conversation — the prefix-cache workload)",
    )
    p.add_argument(
        "--trace", default=None,
        help="trace mode: a reqtrace-*.jsonl file or a "
             "--request-trace-dir directory to replay; omit to "
             "synthesize a mixed-tenant trace from --trace-classes",
    )
    p.add_argument(
        "--trace-classes", default=None,
        help='trace mode synthesis mix, e.g. "interactive=share:0.7,'
             'prompt:32,output:16,tenant:acme;batch=share:0.3,...." '
             "(uses --num-prompts and --qps)",
    )
    p.add_argument(
        "--qps-scale", type=float, default=1.0,
        help="trace mode: divide recorded inter-arrival gaps by this "
             "(2.0 = replay at twice the recorded rate)",
    )
    p.add_argument(
        "--slo", default=None,
        help='SLO targets per class, e.g. "interactive=ttft:200ms,'
             'itl:50ms;batch=ttft:5s" — scored in the trace-mode '
             "scoreboard",
    )
    p.add_argument(
        "--qos-ab", action="store_true",
        help="trace mode: replay the same records twice at >=2x the "
             "recorded rate — QoS layer off (FIFO) then on — and emit "
             "the per-class attainment delta under 'qos_ab'",
    )
    p.set_defaults(func=_run_bench)


def _run_bench(args: argparse.Namespace) -> None:
    from vllm_tpu.benchmarks.run import run_bench

    run_bench(args)


def _add_run_batch(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "run-batch", help="Run an OpenAI batch-format JSONL file offline"
    )
    p.add_argument("-i", "--input-file", required=True)
    p.add_argument("-o", "--output-file", required=True)
    EngineArgs.add_cli_args(p)
    p.set_defaults(func=_run_run_batch)


def _run_run_batch(args: argparse.Namespace) -> None:
    from vllm_tpu.engine.llm_engine import LLMEngine
    from vllm_tpu.entrypoints.run_batch import run_batch

    engine_args = EngineArgs.from_cli_args(args)
    engine = LLMEngine.from_engine_args(engine_args)
    try:
        run_batch(engine, args.input_file, args.output_file, engine_args.model)
    finally:
        engine.shutdown()


def _add_upgrade(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "upgrade",
        help="Drive a health-gated rolling upgrade on a running server")
    p.add_argument(
        "--url", default="http://localhost:8000",
        help="base URL of the serving frontend (POST /admin/upgrade)")
    p.add_argument(
        "--upgrade-checkpoint", default=None,
        help="path to the new weights; omit to cycle the pool onto the "
             "current checkpoint (config-only upgrade)")
    p.add_argument(
        "--upgrade-config", default=None,
        help='JSON object of dotted-path config overrides for the '
             'replacement engines, e.g. '
             '\'{"scheduler_config.max_num_seqs": 8}\'')
    p.add_argument(
        "--upgrade-gate-requests", type=int, default=None,
        help="successful probe requests a newcomer must serve before "
             "promotion (overrides the server default for this cycle)")
    p.add_argument(
        "--upgrade-slo-floor", type=float, default=None,
        help="minimum pool SLO attainment [0,1] required to promote "
             "(overrides the server default for this cycle)")
    p.add_argument(
        "--slots", default=None,
        help='comma-separated engine ids to cycle, e.g. "0,1" '
             "(default: every healthy slot)")
    p.add_argument("--status", action="store_true",
                   help="print the controller snapshot and exit")
    p.add_argument("--abort", action="store_true",
                   help="abort the in-flight cycle at the next safe point")
    p.add_argument(
        "--wait", action="store_true",
        help="after starting, poll until the cycle finishes and exit "
             "non-zero unless the outcome is 'ok'")
    p.set_defaults(func=_run_upgrade)


def _run_upgrade(args: argparse.Namespace) -> None:
    import json
    import time
    import urllib.error
    import urllib.request

    base = args.url.rstrip("/")

    def call(path: str, body: dict | None = None) -> dict:
        req = urllib.request.Request(
            base + path,
            data=(json.dumps(body).encode() if body is not None else None),
            headers={"Content-Type": "application/json"},
            method="POST" if body is not None else "GET",
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            try:
                detail = json.loads(e.read()).get("error", "")
            except Exception:
                detail = ""
            print(f"error: HTTP {e.code} {detail or e.reason}",
                  file=sys.stderr)
            raise SystemExit(1) from e
        except urllib.error.URLError as e:
            print(f"error: cannot reach {base}: {e.reason}",
                  file=sys.stderr)
            raise SystemExit(1) from e

    if args.status:
        print(json.dumps(call("/admin/upgrade"), indent=2))
        return
    if args.abort:
        print(json.dumps(call("/admin/upgrade/abort", {}), indent=2))
        return

    body: dict = {}
    if args.upgrade_checkpoint:
        body["checkpoint"] = args.upgrade_checkpoint
    if args.upgrade_config:
        try:
            config = json.loads(args.upgrade_config)
        except json.JSONDecodeError as e:
            print(f"error: --upgrade-config is not valid JSON: {e}",
                  file=sys.stderr)
            raise SystemExit(2) from e
        if not isinstance(config, dict):
            print("error: --upgrade-config must be a JSON object",
                  file=sys.stderr)
            raise SystemExit(2)
        body["config"] = config
    if args.upgrade_gate_requests is not None:
        body["gate_requests"] = args.upgrade_gate_requests
    if args.upgrade_slo_floor is not None:
        body["slo_floor"] = args.upgrade_slo_floor
    if args.slots:
        try:
            body["slots"] = [int(s) for s in args.slots.split(",") if s]
        except ValueError as e:
            print("error: --slots must be comma-separated integers",
                  file=sys.stderr)
            raise SystemExit(2) from e

    started = call("/admin/upgrade", body)
    print(json.dumps(started, indent=2))
    if not args.wait:
        return
    # Poll until the controller goes idle; the cycle's terminal outcome
    # is the last_outcome the snapshot reports.
    while True:
        time.sleep(1.0)
        snap = call("/admin/upgrade").get("controller", {})
        phase = snap.get("phase", "?")
        print(f"phase={phase} victim={snap.get('victim')} "
              f"newcomer={snap.get('newcomer')} "
              f"slots_done={snap.get('slots_done')}", file=sys.stderr)
        if not snap.get("active"):
            outcome = snap.get("last_outcome")
            print(json.dumps(snap, indent=2))
            if outcome != "ok":
                raise SystemExit(1)
            return


def main(argv: list[str] | None = None) -> None:
    # The start's timeline (tracing.py): `import` ends here, and all the
    # front end does from here to the bound socket is `frontend`, less
    # the engine's phases inside it. `run_server` closes it.
    from vllm_tpu.tracing import startup

    startup.imported()
    startup.begin("frontend")
    parser = argparse.ArgumentParser(prog="vllm-tpu")
    sub = parser.add_subparsers(required=True)
    _add_serve(sub)
    _add_complete(sub)
    _add_bench(sub)
    _add_run_batch(sub)
    _add_upgrade(sub)
    args = parser.parse_args(argv)
    args.func(args)


if __name__ == "__main__":
    main(sys.argv[1:])
