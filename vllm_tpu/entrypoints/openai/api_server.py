"""OpenAI-compatible HTTP server (aiohttp).

Reference analog: ``vllm/entrypoints/openai/api_server.py:671 run_server``
(FastAPI/uvicorn there; this image carries aiohttp). Endpoints:

  POST /v1/completions          (stream + non-stream)
  POST /v1/chat/completions     (stream + non-stream)
  GET  /v1/models
  GET  /health /ping            (JSON liveness + per-engine restart counts)
  GET  /ready                   (503 until all engine cores initialized)
  GET  /metrics                 (Prometheus text format)

Streaming uses SSE (``data: {...}\\n\\n`` ... ``data: [DONE]``), matching the
OpenAI wire format.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
from typing import Any, AsyncIterator

from aiohttp import web

from vllm_tpu.engine.async_llm import AsyncLLM, EngineDeadError
from vllm_tpu.entrypoints.openai.protocol import (
    ChatCompletionRequest,
    CompletionRequest,
    ValidationError,
    now,
    random_id,
)
from vllm_tpu.logger import init_logger
from vllm_tpu.outputs import RequestOutput
from vllm_tpu.resilience import RequestShedError

logger = init_logger(__name__)

# Per-request deadline override header (seconds); the body's deadline_s
# field wins when both are present.
DEADLINE_HEADER = "X-Request-Deadline-S"
# SLO scoreboard labels; the body's slo_class / tenant_id fields win
# when both are present.
SLO_CLASS_HEADER = "X-SLO-Class"
TENANT_HEADER = "X-Tenant-Id"
# QoS scheduling priority (lower = more urgent, 0 = interactive); the
# body's priority field wins when both are present.
PRIORITY_HEADER = "X-Priority"

ENGINE_KEY = web.AppKey("engine", AsyncLLM)
MODEL_KEY = web.AppKey("model_name", str)
METRICS_KEY = web.AppKey("metrics", object)
TOOL_PARSER_KEY = web.AppKey("tool_parser", str)
REASONING_PARSER_KEY = web.AppKey("reasoning_parser", str)
# Multi-frontend topology info for /metrics/cluster: {"port": public
# port, "count": frontend count}. Set by the router launcher; absent in
# single-process mode (the cluster is then this one registry).
CLUSTER_KEY = web.AppKey("cluster", dict)


def _error(status: int, message: str, err_type: str = "invalid_request_error"):
    return web.json_response(
        {"error": {"message": message, "type": err_type, "code": status}},
        status=status,
    )


def _shed_response(e: RequestShedError) -> web.Response:
    """Load-shed / draining rejection: OpenAI-style error body, 429
    (saturated, back off and retry) or 503 (draining, fail over), with a
    Retry-After header either way."""
    err_type = (
        "service_unavailable_error" if e.reason == "draining"
        else "overloaded_error"
    )
    return web.json_response(
        {"error": {
            "message": str(e), "type": err_type, "code": e.http_status,
        }},
        status=e.http_status,
        headers={"Retry-After": str(int(math.ceil(e.retry_after_s)))},
    )


def _apply_deadline_header(request: web.Request, params) -> str | None:
    """Fold the X-Request-Deadline-S header into SamplingParams (body
    field wins). Returns an error message for a malformed header."""
    hdr = request.headers.get(DEADLINE_HEADER)
    if hdr is None or params.deadline_s is not None:
        return None
    try:
        deadline = float(hdr)
    except ValueError:
        return f"{DEADLINE_HEADER} must be a number, got {hdr!r}"
    if deadline <= 0:
        return f"{DEADLINE_HEADER} must be > 0, got {hdr!r}"
    params.deadline_s = deadline
    return None


def _apply_slo_headers(request: web.Request, params) -> str | None:
    """Fold X-SLO-Class / X-Tenant-Id into SamplingParams (body fields
    win). Returns an error message for a malformed header."""
    for header, attr in (
        (SLO_CLASS_HEADER, "slo_class"),
        (TENANT_HEADER, "tenant_id"),
    ):
        hdr = request.headers.get(header)
        if hdr is None or getattr(params, attr) is not None:
            continue
        hdr = hdr.strip()
        if not hdr or len(hdr) > 64:
            return f"{header} must be a non-empty string of <= 64 chars"
        setattr(params, attr, hdr)
    return None


def _apply_priority_header(request: web.Request, params) -> str | None:
    """Fold X-Priority into SamplingParams (body field wins). Returns an
    error message for a malformed header."""
    hdr = request.headers.get(PRIORITY_HEADER)
    if hdr is None or params.priority is not None:
        return None
    try:
        priority = int(hdr.strip())
    except ValueError:
        return f"{PRIORITY_HEADER} must be an integer, got {hdr!r}"
    if not 0 <= priority <= 100:
        return f"{PRIORITY_HEADER} must be in [0, 100], got {hdr!r}"
    params.priority = priority
    return None


# ----------------------------------------------------------------------
# /v1/completions
# ----------------------------------------------------------------------


async def handle_completions(request: web.Request) -> web.StreamResponse:
    engine: AsyncLLM = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        req = CompletionRequest.from_json(body)
    except (json.JSONDecodeError, ValidationError, TypeError, ValueError) as e:
        return _error(400, str(e))

    prompts = _normalize_prompts(req.prompt)
    if req.n < 1:
        return _error(400, "'n' must be >= 1")
    if req.stream and (len(prompts) != 1 or req.n != 1):
        return _error(400, "streaming supports a single prompt with n=1")
    try:
        params = req.to_sampling_params(req.stream)
    except ValueError as e:
        return _error(400, str(e))
    if (msg := _apply_deadline_header(request, params)) is not None:
        return _error(400, msg)
    if (msg := _apply_slo_headers(request, params)) is not None:
        return _error(400, msg)
    if (msg := _apply_priority_header(request, params)) is not None:
        return _error(400, msg)
    req_id = random_id("cmpl")

    if req.stream:
        return await _stream_completion(request, engine, req, prompts[0], params, req_id)

    # n>1: fan out one engine request per sample (parallel sampling; the
    # reference's ParentRequest aggregation, entrypoints-side here). Choices
    # are prompt-major: index = prompt_idx * n + sample_idx.
    from dataclasses import replace as _replace

    jobs = []
    for i, p in enumerate(prompts):
        for j in range(req.n):
            sp = params
            if params.seed is not None and req.n > 1:
                sp = _replace(params, seed=params.seed + j)
            jobs.append(_collect(engine, p, sp, f"{req_id}-{i}-{j}"))
    try:
        results = await asyncio.gather(*jobs)
    except RequestShedError as e:
        return _shed_response(e)
    except EngineDeadError as e:
        return _error(500, str(e), "internal_error")
    choices = []
    n_prompt = n_out = 0
    for idx, out in enumerate(results):
        c = out.outputs[0]
        text = c.text
        if req.echo and out.prompt is not None:
            text = out.prompt + text
        choices.append({
            "index": idx,
            "text": text,
            "logprobs": _completion_logprobs(c) if req.logprobs else None,
            "finish_reason": c.finish_reason or "stop",
        })
        if idx % req.n == 0:
            n_prompt += len(out.prompt_token_ids)
        n_out += len(c.token_ids)
    return web.json_response({
        "id": req_id,
        "object": "text_completion",
        "created": now(),
        "model": req.model or request.app[MODEL_KEY],
        "choices": choices,
        "usage": {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_out,
            "total_tokens": n_prompt + n_out,
        },
    })


async def _stream_completion(
    request, engine, req, prompt, params, req_id
) -> web.StreamResponse:
    # Admission pre-check BEFORE committing to an SSE response: a shed
    # must be a clean 429/503 with Retry-After, not a 200 event stream
    # that errors on its first event. generate() re-checks
    # authoritatively (reserving); the rare lost race is handled below.
    try:
        if hasattr(engine, "check_admission"):
            engine.check_admission()
    except RequestShedError as e:
        return _shed_response(e)
    resp = _sse_response(request)
    await resp.prepare(request)
    model = req.model or request.app[MODEL_KEY]
    try:
        async for out in engine.generate(prompt, params, req_id):
            c = out.outputs[0]
            # Emit on new tokens even when the delta text is empty
            # (tokenizer-less checkpoints): SSE clients measuring
            # TTFT/ITL need one event per decode step.
            if c.text or c.token_ids or out.finished:
                chunk = {
                    "id": req_id,
                    "object": "text_completion",
                    "created": now(),
                    "model": model,
                    "choices": [{
                        "index": 0,
                        "text": c.text,
                        "logprobs": None,
                        "finish_reason": c.finish_reason if out.finished else None,
                    }],
                }
                await _sse_send(resp, chunk)
    except (ConnectionResetError, asyncio.CancelledError):
        return resp
    except RequestShedError as e:
        await _sse_send(resp, {"error": {
            "message": str(e), "type": "overloaded_error",
            "code": e.http_status,
        }})
    except EngineDeadError as e:
        await _sse_send(resp, {"error": {"message": str(e)}})
    await _sse_done(resp)
    return resp


# ----------------------------------------------------------------------
# /v1/chat/completions
# ----------------------------------------------------------------------


async def handle_chat_completions(request: web.Request) -> web.StreamResponse:
    engine: AsyncLLM = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        req = ChatCompletionRequest.from_json(body)
    except (json.JSONDecodeError, ValidationError, TypeError, ValueError) as e:
        return _error(400, str(e))

    tokenizer = engine.tokenizer
    if tokenizer is None:
        return _error(400, "server has no tokenizer; chat API unavailable")
    if isinstance(req.tool_choice, dict):
        return _error(
            400, "forced tool_choice is not supported; use 'auto' or 'none'"
        )
    tools_active = bool(req.tools) and req.tool_choice != "none"
    try:
        template_kwargs = {}
        if tools_active:
            template_kwargs["tools"] = req.tools
        prompt_ids = tokenizer.apply_chat_template(
            req.messages,
            chat_template=req.chat_template,
            add_generation_prompt=req.add_generation_prompt,
            **template_kwargs,
        )
    except Exception as e:
        return _error(400, f"chat template failed: {e}")

    if req.n < 1:
        return _error(400, "'n' must be >= 1")
    if req.stream and req.n != 1:
        return _error(400, "streaming supports n=1")
    try:
        params = req.to_sampling_params(req.stream)
    except ValueError as e:
        return _error(400, str(e))
    if (msg := _apply_deadline_header(request, params)) is not None:
        return _error(400, msg)
    if (msg := _apply_slo_headers(request, params)) is not None:
        return _error(400, msg)
    if (msg := _apply_priority_header(request, params)) is not None:
        return _error(400, msg)
    req_id = random_id("chatcmpl")
    prompt = {"prompt_token_ids": list(prompt_ids)}
    model = req.model or request.app[MODEL_KEY]

    if req.stream:
        try:
            if hasattr(engine, "check_admission"):
                engine.check_admission()
        except RequestShedError as e:
            return _shed_response(e)
        resp = _sse_response(request)
        await resp.prepare(request)
        first = True
        reasoning_name = request.app.get(REASONING_PARSER_KEY)
        tool_parser_name = request.app.get(TOOL_PARSER_KEY)
        if reasoning_name is not None:
            from vllm_tpu.parsers import get_reasoning_parser

            reasoning = get_reasoning_parser(reasoning_name)
        else:
            reasoning = None
        # With tools active, stream incrementally: content before any
        # possible call marker flows immediately; each call is emitted as
        # a tool_calls delta the moment its block closes (reference:
        # extract_tool_calls_streaming in vllm/tool_parsers/).
        buffer_tools = tools_active and tool_parser_name is not None
        stream_tools = None
        n_calls = 0
        if buffer_tools:
            from vllm_tpu.parsers import get_tool_parser
            from vllm_tpu.parsers.tools import StreamingToolParser

            stream_tools = StreamingToolParser(
                get_tool_parser(tool_parser_name)
            )

        async def emit(delta: dict, finish: str | None) -> None:
            await _sse_send(resp, {
                "id": req_id,
                "object": "chat.completion.chunk",
                "created": now(),
                "model": model,
                "choices": [{
                    "index": 0,
                    "delta": delta,
                    "finish_reason": finish,
                }],
            })

        try:
            async for out in engine.generate(prompt, params, req_id):
                c = out.outputs[0]
                delta: dict[str, Any] = {}
                if first:
                    delta["role"] = "assistant"
                    first = False
                text = c.text or ""
                if buffer_tools:
                    # Reasoning splits FIRST (matching the non-streaming
                    # path): tool-call syntax inside a <think> block is
                    # reasoning text, never a real call.
                    if reasoning is not None and text:
                        chunk = reasoning.parse_delta(text)
                        if chunk.reasoning_delta:
                            delta["reasoning_content"] = chunk.reasoning_delta
                        text = chunk.content_delta or ""
                    content_delta, new_calls = stream_tools.push(text)
                    if content_delta:
                        delta["content"] = content_delta
                    if new_calls:
                        delta["tool_calls"] = [
                            {"index": n_calls + i, **t.to_openai()}
                            for i, t in enumerate(new_calls)
                        ]
                        n_calls += len(new_calls)
                elif reasoning is not None and text:
                    chunk = reasoning.parse_delta(text)
                    if chunk.reasoning_delta:
                        delta["reasoning_content"] = chunk.reasoning_delta
                    if chunk.content_delta:
                        delta["content"] = chunk.content_delta
                elif text:
                    delta["content"] = text
                finish = c.finish_reason if out.finished else None
                if out.finished and buffer_tools:
                    # Reasoning already split upstream; the held tail is
                    # plain content + any still-unemitted calls.
                    tail_content, tail_calls = stream_tools.finish()
                    if tail_calls:
                        delta.setdefault("tool_calls", []).extend(
                            {"index": n_calls + i, **t.to_openai()}
                            for i, t in enumerate(tail_calls)
                        )
                        n_calls += len(tail_calls)
                    if tail_content:
                        delta["content"] = (
                            delta.get("content", "") + tail_content
                        )
                    if stream_tools.saw_calls:
                        finish = "tool_calls"
                if delta or out.finished:
                    await emit(delta, finish)
        except (ConnectionResetError, asyncio.CancelledError):
            return resp
        except RequestShedError as e:
            await _sse_send(resp, {"error": {
                "message": str(e), "type": "overloaded_error",
                "code": e.http_status,
            }})
        except EngineDeadError as e:
            await _sse_send(resp, {"error": {"message": str(e)}})
        await _sse_done(resp)
        return resp

    from dataclasses import replace as _replace

    jobs = []
    for j in range(req.n):
        sp = params
        if params.seed is not None and req.n > 1:
            sp = _replace(params, seed=params.seed + j)
        jobs.append(_collect(engine, prompt, sp, f"{req_id}-{j}"))
    try:
        results = await asyncio.gather(*jobs)
    except RequestShedError as e:
        return _shed_response(e)
    except EngineDeadError as e:
        return _error(500, str(e), "internal_error")
    tool_parser_name = request.app.get(TOOL_PARSER_KEY)
    reasoning_name = request.app.get(REASONING_PARSER_KEY)
    choices = []
    for j, out in enumerate(results):
        c = out.outputs[0]
        message: dict[str, Any] = {"role": "assistant", "content": c.text}
        finish = c.finish_reason or "stop"
        if reasoning_name:
            from vllm_tpu.parsers import get_reasoning_parser

            reasoning, content = get_reasoning_parser(
                reasoning_name
            ).parse_full(message["content"] or "")
            message["content"] = content or None
            if reasoning:
                message["reasoning_content"] = reasoning
        if tools_active and tool_parser_name:
            from vllm_tpu.parsers import get_tool_parser

            parsed = get_tool_parser(tool_parser_name).parse(
                message["content"] or ""
            )
            if parsed.tool_calls:
                message["content"] = parsed.content
                message["tool_calls"] = [
                    t.to_openai() for t in parsed.tool_calls
                ]
                finish = "tool_calls"
        choices.append({
            "index": j,
            "message": message,
            "logprobs": _chat_logprobs(c) if req.logprobs else None,
            "finish_reason": finish,
        })
    n_out = sum(len(out.outputs[0].token_ids) for out in results)
    return web.json_response({
        "id": req_id,
        "object": "chat.completion",
        "created": now(),
        "model": model,
        "choices": choices,
        "usage": {
            "prompt_tokens": len(results[0].prompt_token_ids),
            "completion_tokens": n_out,
            "total_tokens": len(results[0].prompt_token_ids) + n_out,
        },
    })


# ----------------------------------------------------------------------
# misc endpoints
# ----------------------------------------------------------------------


async def handle_embeddings(request: web.Request) -> web.Response:
    """OpenAI /v1/embeddings (reference: openai embeddings API)."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError:
        return _error(400, "invalid JSON body")
    try:
        inputs = body.get("input")
        if inputs is None:
            raise ValidationError("'input' is required")
        prompts = _normalize_prompts(inputs)
        from vllm_tpu.sampling_params import PoolingParams, SamplingParams

        # Encoder-only models (BERT family) embed via the CLS pooler by
        # convention; causal LMs via the last-token hidden.
        default_pool = "last"
        try:
            cls = engine.input_processor._model_class()
            if getattr(cls, "is_encoder_only", False) and not getattr(
                cls, "classifier_head", False
            ):
                default_pool = "cls"
        except Exception:  # noqa: BLE001 - resolution is best-effort
            pass
        pooling = PoolingParams(
            pooling_type=body.get("pooling_type", default_pool),
            normalize=bool(body.get("normalize", True)),
        )
    except (ValidationError, ValueError, TypeError) as e:
        return _error(400, str(e))

    async def one(prompt):
        rid = random_id("embd")
        final = None
        async for out in engine.generate(
            prompt, SamplingParams(max_tokens=1), rid,
            pooling_params=pooling,
        ):
            final = out
        if final is None or final.pooled is None:
            raise RuntimeError("pooling request produced no embedding")
        return final

    import asyncio

    try:
        finals = await asyncio.gather(*(one(p) for p in prompts))
    except RequestShedError as e:
        return _shed_response(e)
    except (ValueError, TypeError) as e:
        return _error(400, str(e))
    data = []
    total_tokens = 0
    for i, final in enumerate(finals):
        total_tokens += len(final.prompt_token_ids)
        data.append({
            "object": "embedding",
            "index": i,
            "embedding": final.pooled,
        })
    return web.json_response({
        "object": "list",
        "data": data,
        "model": request.app[MODEL_KEY],
        "usage": {
            "prompt_tokens": total_tokens, "total_tokens": total_tokens,
        },
    })


async def handle_models(request: web.Request) -> web.Response:
    return web.json_response({
        "object": "list",
        "data": [{
            "id": request.app[MODEL_KEY],
            "object": "model",
            "created": now(),
            "owned_by": "vllm-tpu",
        }],
    })


async def _profile_call(engine, status: str, method: str, *args,
                        **echo) -> web.Response:
    """Run ``start_profile`` / ``stop_profile`` OFF the event loop and
    answer with what it returns: the handler only ARMS the call, the
    engine-loop thread executes it between two steps
    (``AsyncLLM.call_on_engine_thread``), and the loop keeps streaming
    tokens while the profiler starts or writes its trace. An engine without
    a loop of its own gets a thread of the default pool."""
    try:
        if hasattr(engine, "call_on_engine_thread"):
            info = await asyncio.wrap_future(
                engine.call_on_engine_thread(method, *args))
        else:
            info = await asyncio.get_running_loop().run_in_executor(
                None, getattr(engine.engine_core, method), *args)
    except Exception as e:
        error = f"{type(e).__name__}: {e}"
        return web.json_response(
            {"error": error},
            status=409 if "ProfilerBusyError" in error else 500)
    return web.json_response(
        {"status": status, **echo,
         **(info if isinstance(info, dict) else {})})


async def handle_start_profile(request: web.Request) -> web.Response:
    """``{"trace_dir": str, "python_tracer_level": 0|1}``, both optional.
    The python tracer is off unless asked for."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    trace_dir, python_tracer_level = None, 0
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            return web.json_response(
                {"error": "request body must be JSON"}, status=400)
        if isinstance(body, dict):
            trace_dir = body.get("trace_dir")
            if trace_dir is not None and not isinstance(trace_dir, str):
                return web.json_response(
                    {"error": "trace_dir must be a string"}, status=400)
            python_tracer_level = body.get("python_tracer_level", 0)
            if python_tracer_level not in (0, 1):
                return web.json_response(
                    {"error": "python_tracer_level must be 0 or 1"},
                    status=400)
    return await _profile_call(
        engine, "profiling started", "start_profile", trace_dir,
        python_tracer_level, trace_dir=trace_dir)


async def handle_stop_profile(request: web.Request) -> web.Response:
    engine: AsyncLLM = request.app[ENGINE_KEY]
    return await _profile_call(engine, "profiling stopped", "stop_profile")


async def handle_health(request: web.Request) -> web.Response:
    """Liveness with per-engine detail: 200 while the server can serve
    anything (including degraded DP, some ranks respawning), 503 once the
    engine is permanently dead. Body is JSON so load balancers and
    operators see WHICH engine is down and how often it restarted."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    status = (
        engine.resilience_status()
        if hasattr(engine, "resilience_status")
        else {"engine_dead": engine._dead, "engines": {}}
    )
    engines = status.get("engines", {})
    dead = status.get("engine_dead", False)
    mesh = status.get("mesh")
    if dead:
        health = "dead"
    elif engines and not all(e.get("up") for e in engines.values()):
        health = "degraded"
    elif mesh is not None and mesh.get("state") in ("degraded",
                                                    "recovering"):
        # A shrunken (or mid-recovery) mesh still serves — at reduced
        # capacity. Liveness stays 200; the state tells operators why
        # throughput dropped.
        health = "degraded"
    else:
        health = "healthy"
    body = {
        "status": health,
        # In the multi-API-server topology each frontend is a separate
        # process behind a shared port; pid lets operators (and the
        # crash-replay test) target a specific shard.
        "pid": os.getpid(),
        "engines": engines,
        "requests_replayed_total": status.get(
            "requests_replayed_total", 0),
        "requests_failed_on_crash_total": status.get(
            "requests_failed_on_crash_total", 0),
        "requests_lost_on_restart_total": status.get(
            "requests_lost_on_restart_total", 0),
    }
    if mesh is not None:
        body["mesh"] = {
            "size": mesh.get("size"),
            "world_size": mesh.get("world_size"),
            "lost_ranks": mesh.get("lost_ranks", []),
            "epoch": mesh.get("epoch", 0),
            "state": mesh.get("state", "healthy"),
            "recoveries_total": mesh.get("recoveries_total", 0),
        }
    # Multi-API-server topology: WHICH frontend shard answered, plus its
    # DP routing-decision view (prefix/least-loaded/round-robin counts).
    client = getattr(engine, "engine_core", None)
    if client is not None and hasattr(client, "client_index"):
        body["api_server_index"] = client.client_index
    if hasattr(engine, "routing_status"):
        routing = engine.routing_status()
        if routing is not None:
            body["routing"] = routing["decisions"]
            body["prefix_index"] = routing.get("index")
    # Elastic capacity: desired vs actual pool size, in-flight scale
    # events, recent event history. Operators watch this during a ramp
    # to see the pool track traffic (and autoscaled LBs use actual).
    if hasattr(engine, "autoscale_status"):
        auto = engine.autoscale_status()
        if auto is not None:
            pool = auto["pool"]
            ctrl = auto.get("controller")
            body["pool"] = {
                "desired": (ctrl["desired"] if ctrl is not None
                            else pool["actual"]),
                "actual": pool["actual"],
                "size": pool["size"],
                "draining": pool["draining"],
                "seeding": pool["seeding"],
                "scale_event": pool["scale_event"],
                "events": pool["events"],
                "autoscale_enabled": auto["enabled"],
            }
            if ctrl is not None:
                body["pool"]["controller"] = ctrl
            if auto.get("kv_occupancy") is not None:
                body["pool"]["kv_occupancy"] = auto["kv_occupancy"]
    # QoS under pressure: current brownout rung + per-tenant WFQ state,
    # so operators see WHY batch traffic is being shed or preempted.
    if hasattr(engine, "qos_status"):
        body["qos"] = engine.qos_status()
    # Zero-downtime operations: package/schema/config/weights identity
    # for the frontend and every engine (a mixed-version pool at a
    # glance), plus the rolling-upgrade cycle state.
    if hasattr(engine, "version_status"):
        body["version"] = engine.version_status()
    if hasattr(engine, "upgrade_status"):
        up = engine.upgrade_status()
        if up is not None:
            body["upgrade"] = {
                "enabled": up["enabled"],
                "controller": up["controller"],
                "config_reloads_total": up["config_reloads_total"],
            }
    return web.json_response(body, status=503 if dead else 200)


async def handle_ready(request: web.Request) -> web.Response:
    """Readiness, distinct from liveness: 503 until every engine is
    initialized and up, so load balancers drain a degraded replica
    without killing it."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    ready = engine.is_ready() if hasattr(engine, "is_ready") else (
        not engine._dead
    )
    body = {"ready": ready}
    if hasattr(engine, "lifecycle_status"):
        ls = engine.lifecycle_status()
        body["draining"] = ls["draining"]
        body["inflight_requests"] = ls["inflight_requests"]
    return web.json_response(body, status=200 if ready else 503)


async def handle_debug_requests(request: web.Request) -> web.Response:
    """Live request introspection: in-flight requests (state, age, tokens
    emitted, KV blocks held) plus a bounded ring of recently finished
    requests with their per-phase timing breakdown."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    if not hasattr(engine, "debug_requests"):
        return web.json_response(
            {"error": "engine does not support request introspection"},
            status=501)
    snapshot = engine.debug_requests()
    if hasattr(engine, "lifecycle_status"):
        snapshot["lifecycle"] = engine.lifecycle_status()
    return web.json_response(snapshot)


async def handle_debug_deadletter(request: web.Request) -> web.Response:
    """Dead-letter introspection: requests quarantined as poison (they
    repeatedly crashed the engine that executed them), with strike
    history and the live bisection state. Re-admission goes through
    ``tools/deadletter.py``."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    if not hasattr(engine, "debug_deadletter"):
        return web.json_response(
            {"error": "engine does not support quarantine introspection"},
            status=501)
    return web.json_response(engine.debug_deadletter())


async def handle_admin_upgrade(request: web.Request) -> web.Response:
    """POST /admin/upgrade: start a health-gated rolling upgrade.
    Body: ``{"checkpoint": path?, "config": {dotted.path: value}?,
    "slots": [engine_id]?}``. The pool cycles one slot at a time — boot
    a gated replacement with the new checkpoint/config, probe it,
    shift routing, drain the old engine — rolling back automatically on
    a failed gate. One cycle at a time; bad input is a 400 here, not a
    failed boot mid-cycle."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    if (not hasattr(engine, "upgrade_status")
            or engine.upgrade_status() is None):
        return web.json_response(
            {"error": "rolling upgrades need a data-parallel engine "
             "pool (--data-parallel-engines >= 2)"}, status=501)
    body: dict = {}
    if request.can_read_body:
        try:
            parsed = await request.json()
        except Exception:
            return web.json_response(
                {"error": "request body must be JSON"}, status=400)
        if isinstance(parsed, dict):
            body = parsed
    config = body.get("config")
    if config is not None and not isinstance(config, dict):
        return web.json_response(
            {"error": "config must be an object of dotted-path: value "
             "pairs"}, status=400)
    slots = body.get("slots")
    if slots is not None and not (
            isinstance(slots, list)
            and all(isinstance(s, int) for s in slots)):
        return web.json_response(
            {"error": "slots must be a list of engine ids"}, status=400)
    gate_requests = body.get("gate_requests")
    if gate_requests is not None and not isinstance(gate_requests, int):
        return web.json_response(
            {"error": "gate_requests must be an integer"}, status=400)
    slo_floor = body.get("slo_floor")
    if slo_floor is not None and not isinstance(slo_floor, (int, float)):
        return web.json_response(
            {"error": "slo_floor must be a number"}, status=400)
    try:
        started = engine.start_upgrade(
            checkpoint=body.get("checkpoint"), config=config,
            slots=slots, gate_requests=gate_requests,
            slo_floor=slo_floor)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.json_response(started)


async def handle_admin_upgrade_status(
        request: web.Request) -> web.Response:
    """GET /admin/upgrade: the rolling-upgrade controller snapshot
    (phase, victim/newcomer, probe counts, gate budget, outcomes)."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    status = (engine.upgrade_status()
              if hasattr(engine, "upgrade_status") else None)
    if status is None:
        return web.json_response(
            {"error": "rolling upgrades need a data-parallel engine "
             "pool"}, status=501)
    return web.json_response(status)


async def handle_admin_upgrade_abort(
        request: web.Request) -> web.Response:
    """POST /admin/upgrade/abort: stop the in-flight cycle at the next
    safe point (a gated newcomer rolls back; a promoted slot finishes
    its drain first)."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    if not hasattr(engine, "abort_upgrade"):
        return web.json_response(
            {"error": "engine does not support rolling upgrades"},
            status=501)
    return web.json_response(engine.abort_upgrade())


async def handle_admin_config(request: web.Request) -> web.Response:
    """POST /admin/config: apply a live-updatable config subset
    pool-wide without restart (body: ``{key: value}``). Unknown or
    out-of-range keys reject the WHOLE request with a 400 listing the
    updatable set. GET lists the vetted keys and reload counters."""
    engine: AsyncLLM = request.app[ENGINE_KEY]
    from vllm_tpu.resilience import LiveConfigError, live_config_keys

    if request.method == "GET" or not hasattr(engine,
                                              "set_live_config"):
        if request.method != "GET":
            return web.json_response(
                {"error": "engine does not support live config"},
                status=501)
        return web.json_response({
            "live_config_keys": live_config_keys(),
            "config_reloads_total": dict(
                getattr(engine, "config_reloads_total", None) or {}),
        })
    try:
        parsed = await request.json()
    except Exception:
        return web.json_response(
            {"error": "request body must be JSON"}, status=400)
    if not isinstance(parsed, dict):
        return web.json_response(
            {"error": "body must be an object of key: value pairs"},
            status=400)
    loop = asyncio.get_running_loop()
    try:
        # Blocks briefly on the engine-loop handshake for engine-scope
        # keys — run off the event loop.
        result = await loop.run_in_executor(
            None, engine.set_live_config, parsed)
    except LiveConfigError as e:
        return web.json_response(
            {"error": str(e), "keys": e.keys,
             "live_config_keys": live_config_keys()}, status=400)
    except Exception as e:
        return web.json_response({"error": str(e)}, status=500)
    return web.json_response(result)


async def handle_metrics(request: web.Request) -> web.Response:
    reg = request.app.get(METRICS_KEY)
    text = reg.render() if reg is not None else ""
    return web.Response(text=text, content_type="text/plain")


async def handle_metrics_cluster(request: web.Request) -> web.Response:
    """Pool-wide metrics: scrape every sibling frontend's admin-port
    /metrics and merge (counters/histograms summed, gauges re-labeled
    per frontend). Single-process topology degrades to the local
    registry — the cluster of one."""
    cluster = request.app.get(CLUSTER_KEY)
    reg = request.app.get(METRICS_KEY)
    if not cluster or cluster.get("count", 1) <= 1:
        text = reg.render() if reg is not None else ""
        return web.Response(text=text, content_type="text/plain")

    import aiohttp

    from vllm_tpu.metrics.prometheus import merge_expositions
    from vllm_tpu.router.topology import admin_port_for

    port, count = cluster["port"], cluster["count"]
    texts: list[str | None] = [None] * count
    timeout = aiohttp.ClientTimeout(total=5)

    async def scrape(session, k: int) -> None:
        url = f"http://127.0.0.1:{admin_port_for(port, k)}/metrics"
        try:
            async with session.get(url) as rsp:
                if rsp.status == 200:
                    texts[k] = await rsp.text()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            pass  # a dead/respawning frontend drops out of the merge

    async with aiohttp.ClientSession(timeout=timeout) as session:
        await asyncio.gather(*(scrape(session, k) for k in range(count)))
    merged = merge_expositions(
        {str(k): t for k, t in enumerate(texts) if t is not None}
    )
    header = (
        f"# cluster: {sum(t is not None for t in texts)}/{count} "
        "frontends scraped\n"
    )
    return web.Response(text=header + merged, content_type="text/plain")


# ----------------------------------------------------------------------
# plumbing
# ----------------------------------------------------------------------


def _normalize_prompts(prompt: Any) -> list[Any]:
    if isinstance(prompt, str):
        return [prompt]
    if isinstance(prompt, list):
        if not prompt:
            raise ValidationError("empty prompt")
        if isinstance(prompt[0], int):
            return [{"prompt_token_ids": prompt}]
        if isinstance(prompt[0], str):
            return list(prompt)
        if isinstance(prompt[0], list):
            return [{"prompt_token_ids": p} for p in prompt]
    raise ValidationError("prompt must be str | [str] | [int] | [[int]]")


async def _collect(engine, prompt, params, req_id) -> RequestOutput:
    final = None
    async for out in engine.generate(prompt, params, req_id):
        final = out
    assert final is not None
    return final


def _completion_logprobs(c) -> dict | None:
    """`c.logprobs[i]` is the top-k dict for sampled token `c.token_ids[i]`."""
    if not c.logprobs:
        return None
    token_logprobs, tokens, top = [], [], []
    for tid, lp_dict in zip(c.token_ids, c.logprobs):
        # Keep arrays aligned with token positions: a position whose sampled
        # logprob is missing gets a null entry rather than being dropped.
        sampled = lp_dict.get(tid)
        tokens.append(
            (sampled.decoded_token if sampled else None) or str(tid)
        )
        token_logprobs.append(sampled.logprob if sampled else None)
        top.append({
            (lp.decoded_token or str(t)): lp.logprob
            for t, lp in lp_dict.items()
        })
    return {
        "tokens": tokens,
        "token_logprobs": token_logprobs,
        "top_logprobs": top,
        "text_offset": [],
    }


def _chat_logprobs(c) -> dict | None:
    if not c.logprobs:
        return None
    content = []
    for tid, lp_dict in zip(c.token_ids, c.logprobs):
        # Null placeholder instead of dropping: keeps content aligned with
        # the generated token positions.
        sampled = lp_dict.get(tid)
        content.append({
            "token": (sampled.decoded_token if sampled else None) or str(tid),
            "logprob": sampled.logprob if sampled else None,
            "top_logprobs": [
                {"token": lp.decoded_token or str(t), "logprob": lp.logprob}
                for t, lp in lp_dict.items()
            ],
        })
    return {"content": content}


def _sse_response(request) -> web.StreamResponse:
    return web.StreamResponse(
        status=200,
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        },
    )


async def _sse_send(resp: web.StreamResponse, obj: dict) -> None:
    await resp.write(f"data: {json.dumps(obj)}\n\n".encode())


async def _sse_done(resp: web.StreamResponse) -> None:
    await resp.write(b"data: [DONE]\n\n")
    await resp.write_eof()


def build_app(engine: AsyncLLM, model_name: str, metrics=None,
              tool_parser: str | None = None,
              reasoning_parser: str | None = None) -> web.Application:
    app = web.Application()
    app[ENGINE_KEY] = engine
    app[MODEL_KEY] = model_name
    if metrics is not None:
        app[METRICS_KEY] = metrics
    if tool_parser:
        app[TOOL_PARSER_KEY] = tool_parser
    if reasoning_parser:
        app[REASONING_PARSER_KEY] = reasoning_parser
    app.router.add_post("/v1/completions", handle_completions)
    app.router.add_post("/v1/embeddings", handle_embeddings)
    from vllm_tpu.entrypoints.anthropic_api import handle_messages

    app.router.add_post("/v1/messages", handle_messages)
    app.router.add_post("/start_profile", handle_start_profile)
    app.router.add_post("/stop_profile", handle_stop_profile)
    app.router.add_post("/v1/chat/completions", handle_chat_completions)
    app.router.add_get("/v1/models", handle_models)
    app.router.add_get("/health", handle_health)
    app.router.add_get("/ping", handle_health)
    app.router.add_get("/ready", handle_ready)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/metrics/cluster", handle_metrics_cluster)
    app.router.add_get("/debug/requests", handle_debug_requests)
    app.router.add_get("/debug/deadletter", handle_debug_deadletter)
    app.router.add_get("/admin/upgrade", handle_admin_upgrade_status)
    app.router.add_post("/admin/upgrade", handle_admin_upgrade)
    app.router.add_post("/admin/upgrade/abort",
                        handle_admin_upgrade_abort)
    app.router.add_get("/admin/config", handle_admin_config)
    app.router.add_post("/admin/config", handle_admin_config)
    from vllm_tpu.entrypoints.openai.extra_apis import (
        handle_realtime,
        handle_responses,
        handle_score,
        handle_transcriptions,
        handle_translations,
    )

    app.router.add_post("/v1/responses", handle_responses)
    app.router.add_post("/score", handle_score)
    app.router.add_post("/v1/score", handle_score)
    app.router.add_post("/v1/audio/transcriptions", handle_transcriptions)
    app.router.add_post("/v1/audio/translations", handle_translations)
    app.router.add_get("/v1/realtime", handle_realtime)
    return app


def run_server(engine_args, host: str = "0.0.0.0", port: int = 8000,
               tool_parser: str | None = None,
               reasoning_parser: str | None = None) -> None:
    """Serve until SIGTERM/SIGINT, then drain gracefully.

    The drain sequence (see README "Overload & lifecycle"): the signal
    closes ADMISSION, not the listener — new requests get a clean 503 +
    Retry-After (and /ready flips 503 so the load balancer stops routing
    here) while in-flight requests keep streaming. Supervisor respawns
    are suspended so teardown can never race a respawn back to life.
    After the drain budget, stragglers are finished with
    finish_reason="timeout"; only then do the listener and engine come
    down. web.run_app would do the opposite — stop the listener first,
    turning every late request into a connection error.
    """
    import signal

    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    # Frontend scale-out: N API-server processes sharing the listen
    # socket in front of one shared engine pool (vllm_tpu/router/).
    # The launcher owns the whole topology and never returns.
    if getattr(engine_args, "api_server_count", 1) > 1:
        from vllm_tpu.router.topology import run_multi_server

        run_multi_server(
            engine_args, host=host, port=port,
            tool_parser=tool_parser, reasoning_parser=reasoning_parser,
        )
        return

    engine = AsyncLLM.from_engine_args(engine_args)
    metrics = PrometheusRegistry(engine)
    engine.stat_loggers.append(metrics)
    app = build_app(
        engine, engine_args.model, metrics,
        tool_parser=tool_parser, reasoning_parser=reasoning_parser,
    )
    logger.info("serving %s on %s:%d", engine_args.model, host, port)

    async def _serve() -> None:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, host, port)
        await site.start()
        # The socket is bound and /health answers: the start is over.
        from vllm_tpu.tracing import startup

        metrics.record_startup(startup.ready())
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # pragma: no cover - non-unix
                pass
        await stop.wait()
        logger.info("shutdown signal received; draining")
        await engine.drain()
        await runner.cleanup()

    try:
        asyncio.run(_serve())
    finally:
        engine.shutdown()
