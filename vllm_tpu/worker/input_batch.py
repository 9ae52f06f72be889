"""Persistent host-side batch state, fixed row per request.

Reference analog: ``vllm/v1/worker/gpu_input_batch.py`` with the Model
Runner V2 refinement (``docs/design/model_runner_v2.md``): each request owns
a stable dense row; removal swap-condenses from the tail so per-step input
assembly is contiguous numpy slicing (the host has ONE core on TPU VMs —
everything here is vectorized, no per-token Python).
"""

from __future__ import annotations

import numpy as np

from vllm_tpu.core.sched_output import NewRequestData
from vllm_tpu.sampling_params import SamplingParams


class CachedRequestState:
    __slots__ = (
        "req_id",
        "sampling_params",
        "num_computed_tokens",
        "num_tokens",
        "generated",
        "in_batch_row",
        "eos_token_id",
        "needs_logit_adjust",
        "logit_bias_items",
        "pooling_params",
        "mm_inputs",
        "mrope",
    )

    def __init__(self, req_id: str, sampling_params: SamplingParams,
                 eos_token_id: int | None = None,
                 pooling_params=None) -> None:
        self.req_id = req_id
        self.sampling_params = sampling_params
        self.pooling_params = pooling_params
        self.num_computed_tokens = 0
        self.num_tokens = 0
        self.generated = 0  # sampled so far (drives seeded PRNG streams)
        self.in_batch_row = -1
        self.eos_token_id = eos_token_id
        self.mm_inputs = None  # multimodal placeholder spans + pixels
        self.mrope = None  # Qwen2-VL: ([3, prompt_len] pos table, delta)
        p = sampling_params
        # Per-request logits-processor work (bias / bans / min-tokens EOS
        # suppression); cached so the no-adjustment common path costs one
        # bool check per row.
        self.needs_logit_adjust = bool(
            p.logit_bias
            or p.bad_words_token_ids
            or (p.min_tokens and (eos_token_id is not None
                                  or p.stop_token_ids))
        )
        self.logit_bias_items = (
            [(int(t), float(v)) for t, v in p.logit_bias.items()]
            if p.logit_bias
            else []
        )


class InputBatch:
    def __init__(
        self,
        max_num_reqs: int,
        max_model_len: int,
        max_blocks_per_req: int,
        num_kv_groups: int = 1,
    ) -> None:
        self.max_num_reqs = max_num_reqs
        self.max_model_len = max_model_len
        self.max_blocks_per_req = max_blocks_per_req

        self.num_reqs = 0
        self.req_ids: list[str | None] = [None] * max_num_reqs
        self.req_states: dict[str, CachedRequestState] = {}

        n, m = max_num_reqs, max_model_len
        self.token_ids = np.zeros((n, m), dtype=np.int32)
        self.num_tokens = np.zeros(n, dtype=np.int32)
        self.num_computed_tokens = np.zeros(n, dtype=np.int32)
        # A block table a KV group; a group's row holds one entry a page
        # of the sequence, a page the tokens of ITS group (groups of one
        # page size hold the same count, a group of twice the tokens a
        # page half of it). ``block_table`` and ``num_blocks`` are group
        # 0's (the only one of a uniform model).
        self.block_tables = [
            np.zeros((n, max_blocks_per_req), dtype=np.int32)
            for _ in range(num_kv_groups)
        ]
        self.block_table = self.block_tables[0]
        self.num_blocks_by_group = np.zeros((num_kv_groups, n), dtype=np.int32)
        self.num_blocks = self.num_blocks_by_group[0]

        # Sampling columns.
        self.temperature = np.zeros(n, dtype=np.float32)
        self.top_k = np.zeros(n, dtype=np.int32)
        self.top_p = np.ones(n, dtype=np.float32)
        self.min_p = np.zeros(n, dtype=np.float32)
        self.presence_penalty = np.zeros(n, dtype=np.float32)
        self.frequency_penalty = np.zeros(n, dtype=np.float32)
        self.repetition_penalty = np.ones(n, dtype=np.float32)
        self.seeds = np.zeros(n, dtype=np.uint32)
        self.num_logprobs = np.zeros(n, dtype=np.int32)  # 0 => off
        self.lora_slot = np.zeros(n, dtype=np.int32)  # 0 => no adapter
        # Dense mirror of CachedRequestState.generated (seeded PRNG
        # counter) so step assembly gathers it without a Python row loop.
        self.generated = np.zeros(n, dtype=np.int32)

    # ------------------------------------------------------------------

    def add_request(self, data: NewRequestData) -> int:
        row = self.num_reqs
        assert row < self.max_num_reqs
        self.num_reqs += 1
        req_id = data.req_id
        self.req_ids[row] = req_id

        state = CachedRequestState(
            req_id, data.sampling_params, data.eos_token_id,
            getattr(data, "pooling_params", None),
        )
        state.mm_inputs = getattr(data, "mm_inputs", None)
        state.in_batch_row = row
        state.num_computed_tokens = data.num_computed_tokens
        state.num_tokens = len(data.prompt_token_ids)
        self.req_states[req_id] = state

        n_tok = len(data.prompt_token_ids)
        self.token_ids[row, :n_tok] = data.prompt_token_ids
        self.num_tokens[row] = n_tok
        self.num_computed_tokens[row] = data.num_computed_tokens
        self._set_blocks(row, 0, data.block_ids)

        p = data.sampling_params
        self.temperature[row] = p.temperature
        self.top_k[row] = p.top_k
        self.top_p[row] = p.top_p
        self.min_p[row] = p.min_p
        self.presence_penalty[row] = p.presence_penalty
        self.frequency_penalty[row] = p.frequency_penalty
        self.repetition_penalty[row] = p.repetition_penalty
        seed = p.seed if p.seed is not None else (0xC0FFEE ^ hash(req_id))
        self.seeds[row] = np.uint32(seed & 0xFFFFFFFF)
        self.num_logprobs[row] = p.logprobs or 0
        self.generated[row] = 0
        return row

    def remove_request(self, req_id: str) -> None:
        state = self.req_states.pop(req_id, None)
        if state is None:
            return
        row = state.in_batch_row
        last = self.num_reqs - 1
        if row != last:
            # Swap-condense: move the tail row into the vacated slot.
            moved_id = self.req_ids[last]
            assert moved_id is not None
            for col in (self.token_ids, *self.block_tables):
                col[row] = col[last]
            for vec in (
                self.num_tokens,
                self.num_computed_tokens,
                *self.num_blocks_by_group,
                self.temperature,
                self.top_k,
                self.top_p,
                self.min_p,
                self.presence_penalty,
                self.frequency_penalty,
                self.repetition_penalty,
                self.seeds,
                self.num_logprobs,
                self.lora_slot,
                self.generated,
            ):
                vec[row] = vec[last]
            self.req_ids[row] = moved_id
            self.req_states[moved_id].in_batch_row = row
        self.req_ids[last] = None
        self.num_reqs -= 1

    # ------------------------------------------------------------------
    # Per-step updates (CachedRequestData application)
    # ------------------------------------------------------------------

    def _set_blocks(
        self, row: int, start: int | None, block_ids: tuple[list[int], ...]
    ) -> None:
        """Write ``block_ids`` (one list a KV group) into the row's tables
        from entry ``start`` on; None = after what each group holds (the
        groups' counts differ where their pages hold unlike tokens)."""
        for table, held, ids in zip(
                self.block_tables, self.num_blocks_by_group, block_ids,
                strict=True):
            at = int(held[row]) if start is None else start
            table[row, at : at + len(ids)] = ids
            held[row] = at + len(ids)

    def append_block_ids(
        self, req_id: str, new_block_ids: tuple[list[int], ...]
    ) -> None:
        row = self.req_states[req_id].in_batch_row
        self._set_blocks(row, None, new_block_ids)

    def reset_for_resume(
        self, req_id: str, token_ids: list[int],
        block_ids: tuple[list[int], ...], num_computed: int
    ) -> None:
        """Preemption-resume: block table and computed count restart."""
        state = self.req_states[req_id]
        row = state.in_batch_row
        self.token_ids[row, : len(token_ids)] = token_ids
        self.num_tokens[row] = len(token_ids)
        state.num_tokens = len(token_ids)
        self._set_blocks(row, 0, block_ids)
        self.num_computed_tokens[row] = num_computed
        state.num_computed_tokens = num_computed

    def set_num_computed(self, req_id: str, num_computed: int) -> None:
        state = self.req_states[req_id]
        self.num_computed_tokens[state.in_batch_row] = num_computed
        state.num_computed_tokens = num_computed

    def append_token(self, req_id: str, token_id: int) -> None:
        state = self.req_states[req_id]
        row = state.in_batch_row
        n = self.num_tokens[row]
        if n < self.max_model_len:
            self.token_ids[row, n] = token_id
        self.num_tokens[row] = n + 1
        state.num_tokens = int(n) + 1
        state.generated += 1
        self.generated[row] = state.generated

    def row_of(self, req_id: str) -> int:
        return self.req_states[req_id].in_batch_row
