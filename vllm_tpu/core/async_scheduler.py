"""Async (lag-1 pipelined) scheduler.

Reference analog: ``vllm/v1/core/sched/async_scheduler.py`` (60 LoC
subclass). Step N+1 is scheduled before step N's sampled tokens reach the
host: computed-token counts advance at schedule time, and a decode whose
input token is still in flight is scheduled with an output *placeholder* —
the model runner feeds the token device-side from the previous step's
``sampled`` array, so no host roundtrip sits on the critical path.

Invariant: ``num_output_placeholders`` = sampling steps dispatched for the
request minus output tokens materialized by ``update_from_output``. The
scheduling formula ``num_tokens_with_spec + placeholders - computed``
yields 0 once a request is 2 steps ahead, bounding the pipeline to lag 1.
"""

from __future__ import annotations

from vllm_tpu.core.scheduler import Scheduler
from vllm_tpu.request import Request


class AsyncScheduler(Scheduler):
    async_scheduling = True

    def _after_schedule(self, request: Request, num_new_tokens: int) -> None:
        request.num_computed_tokens += num_new_tokens
        if (
            request.num_computed_tokens >= request.num_tokens
            and request.pooling_params is None  # pooling never samples
        ):
            # This step samples an output token not yet known host-side.
            request.num_output_placeholders += 1
            request.num_inflight_steps += 1
