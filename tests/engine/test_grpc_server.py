"""gRPC entrypoint (reference: vllm/entrypoints/grpc_server.py): JSON-
over-gRPC generate/health/models service backed by AsyncLLM."""

from __future__ import annotations

import asyncio
import json
import socket
import threading

import grpc
import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu.engine.arg_utils import AsyncEngineArgs


@pytest.fixture(scope="module")
def grpc_addr(tmp_path_factory):
    ckpt = tiny_llama_dir(tmp_path_factory.mktemp("tiny_grpc"))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    addr = f"127.0.0.1:{port}"
    ready = threading.Event()
    stop: list = []

    def serve():
        async def run():
            from vllm_tpu.engine.async_llm import AsyncLLM
            from vllm_tpu.entrypoints.grpc_server import make_server

            engine = AsyncLLM.from_engine_args(AsyncEngineArgs(
                model=ckpt, dtype="float32", max_model_len=128,
                block_size=16, num_gpu_blocks_override=64, max_num_seqs=4,
                max_num_batched_tokens=128,
            ))
            server = make_server(engine, ckpt)
            server.add_insecure_port(addr)
            await server.start()
            loop = asyncio.get_running_loop()
            stop.append(lambda: asyncio.run_coroutine_threadsafe(
                server.stop(0.1), loop
            ))
            ready.set()
            await server.wait_for_termination()
            # Or its loop thread lives on in this worker, and writes its
            # spans into the trace of whichever later test turns tracing on.
            engine.shutdown()

        asyncio.run(run())

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    assert ready.wait(timeout=180), "grpc server failed to start"
    yield addr
    if stop:
        stop[0]().result(timeout=10)
    t.join(timeout=30)


def _ident(b: bytes) -> bytes:
    return b


def test_grpc_health_and_models(grpc_addr):
    with grpc.insecure_channel(grpc_addr) as ch:
        health = ch.unary_unary(
            "/vllmtpu.LLMJson/Health", request_serializer=_ident,
            response_deserializer=_ident,
        )
        assert json.loads(health(b"{}"))["status"] == "SERVING"
        models = ch.unary_unary(
            "/vllmtpu.LLMJson/Models", request_serializer=_ident,
            response_deserializer=_ident,
        )
        assert len(json.loads(models(b"{}"))["models"]) == 1


def test_grpc_generate_stream(grpc_addr):
    with grpc.insecure_channel(grpc_addr) as ch:
        gen = ch.unary_stream(
            "/vllmtpu.LLMJson/Generate", request_serializer=_ident,
            response_deserializer=_ident,
        )
        req = {
            "prompt_token_ids": [5, 9, 11],
            "sampling_params": {
                "temperature": 0.0, "max_tokens": 6, "ignore_eos": True,
            },
        }
        msgs = [json.loads(m) for m in gen(json.dumps(req).encode())]
        assert msgs and msgs[-1]["finished"]
        # token_ids stream as DELTAS; the concatenation is the generation.
        all_tokens = [t for m in msgs for t in m["token_ids"]]
        assert len(all_tokens) == 6
        assert msgs[-1]["finish_reason"] == "length"


def test_grpc_bad_request_is_invalid_argument(grpc_addr):
    with grpc.insecure_channel(grpc_addr) as ch:
        gen = ch.unary_stream(
            "/vllmtpu.LLMJson/Generate", request_serializer=_ident,
            response_deserializer=_ident,
        )
        with pytest.raises(grpc.RpcError) as err:
            list(gen(json.dumps({
                "prompt_token_ids": [1],
                "sampling_params": {"definitely_not_a_knob": 1},
            }).encode()))
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT


# ----------------------------------------------------------------------
# Typed protobuf service (canonical /vllmtpu.LLM, proto/llm.proto stubs)
# ----------------------------------------------------------------------

def test_typed_health_and_models(grpc_addr):
    from vllm_tpu.entrypoints.proto import llm_pb2
    from vllm_tpu.entrypoints.proto.llm_pb2_grpc import LLMStub

    with grpc.insecure_channel(grpc_addr) as ch:
        stub = LLMStub(ch)
        assert stub.Health(llm_pb2.HealthRequest()).status == "SERVING"
        models = stub.Models(llm_pb2.ModelsRequest()).models
        assert len(models) == 1


def test_typed_generate_stream(grpc_addr):
    from vllm_tpu.entrypoints.proto import llm_pb2
    from vllm_tpu.entrypoints.proto.llm_pb2_grpc import LLMStub

    req = llm_pb2.GenerateRequest(
        prompt_token_ids=[3, 5, 7, 11],
        request_id="typed-1",
        sampling_params=llm_pb2.SamplingParamsProto(
            temperature=0.0, max_tokens=6, ignore_eos=True,
        ),
    )
    with grpc.insecure_channel(grpc_addr) as ch:
        stub = LLMStub(ch)
        tokens = []
        finished = False
        for resp in stub.Generate(req):
            assert resp.request_id == "typed-1"
            tokens.extend(resp.token_ids)
            finished = resp.finished
        assert finished and len(tokens) == 6


def test_typed_matches_json(grpc_addr):
    """Same request through the typed and JSON services -> same tokens."""
    from vllm_tpu.entrypoints.proto import llm_pb2
    from vllm_tpu.entrypoints.proto.llm_pb2_grpc import LLMStub

    with grpc.insecure_channel(grpc_addr) as ch:
        stub = LLMStub(ch)
        typed = []
        for resp in stub.Generate(llm_pb2.GenerateRequest(
            prompt_token_ids=[2, 4, 6],
            sampling_params=llm_pb2.SamplingParamsProto(
                temperature=0.0, max_tokens=5, ignore_eos=True,
            ),
        )):
            typed.extend(resp.token_ids)

        gen = ch.unary_stream(
            "/vllmtpu.LLMJson/Generate", request_serializer=_ident,
            response_deserializer=_ident,
        )
        js = []
        for raw in gen(json.dumps({
            "prompt_token_ids": [2, 4, 6],
            "sampling_params": {
                "temperature": 0.0, "max_tokens": 5, "ignore_eos": True,
            },
        }).encode()):
            js.extend(json.loads(raw)["token_ids"])
    assert typed == js


def test_typed_rejects_empty_prompt(grpc_addr):
    from vllm_tpu.entrypoints.proto import llm_pb2
    from vllm_tpu.entrypoints.proto.llm_pb2_grpc import LLMStub

    with grpc.insecure_channel(grpc_addr) as ch:
        stub = LLMStub(ch)
        with pytest.raises(grpc.RpcError) as exc:
            list(stub.Generate(llm_pb2.GenerateRequest()))
        assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_proto_logprobs_zero_expressible():
    """logprobs=0 (sampled-token logprob only) survives the typed proto
    (ADVICE r4 #2: presence-gated, not truthiness-gated)."""
    from vllm_tpu.entrypoints.grpc_server import _params_from_proto
    from vllm_tpu.entrypoints.proto import llm_pb2

    sp = llm_pb2.SamplingParamsProto()
    sp.logprobs = 0
    sp.min_tokens = 0
    params = _params_from_proto(sp)
    assert params.logprobs == 0  # explicit 0, not None
    unset = _params_from_proto(llm_pb2.SamplingParamsProto())
    assert unset.logprobs is None


def test_json_on_typed_service_gets_migration_hint():
    """Legacy JSON clients calling /vllmtpu.LLM get a descriptive
    FAILED_PRECONDITION pointing at /vllmtpu.LLMJson (ADVICE r4 #4)."""
    import json as _json

    import grpc
    import pytest

    from vllm_tpu.entrypoints.proto.llm_pb2_grpc import (
        _guard_unary,
        _lenient,
        JsonPayloadOnTypedService,
    )
    from vllm_tpu.entrypoints.proto import llm_pb2

    de = _lenient(llm_pb2.GenerateRequest)
    req = de(_json.dumps({"prompt": "hi"}).encode())
    assert isinstance(req, JsonPayloadOnTypedService)
    # Valid protobuf still parses.
    msg = llm_pb2.GenerateRequest(prompt="hi")
    assert de(msg.SerializeToString()).prompt == "hi"

    class Ctx:
        def __init__(self):
            self.code = self.details = None

        async def abort(self, code, details):
            self.code, self.details = code, details
            raise grpc.RpcError(details)

    async def handler(request, context):
        return "should-not-run"

    import asyncio

    ctx = Ctx()
    with pytest.raises(grpc.RpcError, match="LLMJson"):
        asyncio.run(_guard_unary(handler)(JsonPayloadOnTypedService(), ctx))
    assert ctx.code == grpc.StatusCode.FAILED_PRECONDITION
