"""Request-level KV block allocation with prefix-cache reuse.

Reference analog: ``vllm/v1/core/kv_cache_manager.py:106``. Round-1 scope is
a single full-attention KV group (the reference's UnitaryKVCacheCoordinator
path); the interface leaves room for hybrid groups (sliding window, mamba).
"""

from __future__ import annotations

from dataclasses import dataclass
from math import ceil

from vllm_tpu.core.block_pool import BlockPool
from vllm_tpu.core.kv_cache_utils import KVCacheBlock
from vllm_tpu.logger import init_logger
from vllm_tpu.request import Request

logger = init_logger(__name__)


@dataclass
class PrefixCacheStats:
    requests: int = 0
    queries: int = 0  # tokens eligible for lookup
    hits: int = 0  # tokens served from cache

    def observe(self, queries: int, hits: int) -> None:
        self.requests += 1
        self.queries += queries
        self.hits += hits

    @property
    def hit_rate(self) -> float:
        return self.hits / self.queries if self.queries else 0.0


class KVCacheManager:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_caching: bool = True,
        sliding_window: int | None = None,
        event_sink=None,
        num_stripes: int = 1,
    ) -> None:
        self.block_size = block_size
        # Context parallelism: a request's k-th context block comes from
        # pool color k % num_stripes (= the cp rank holding that page).
        self.num_stripes = num_stripes
        self.sliding_window = sliding_window
        self.enable_caching = enable_caching
        self.block_pool = BlockPool(
            num_blocks, enable_caching,
            event_sink=event_sink, block_size=block_size,
            num_colors=num_stripes,
        )
        # Per-attention-type policy (reference:
        # single_type_kv_cache_manager.py family under the unitary
        # coordinator): full attention vs sliding window (window-aware
        # prefix hits + out-of-window freeing). Hybrid per-group
        # coordination plugs in here.
        from vllm_tpu.core.single_type_managers import (
            FullAttentionManager,
            SlidingWindowManager,
        )

        self.type_manager = (
            SlidingWindowManager(self.block_pool, block_size, sliding_window)
            if sliding_window is not None
            else FullAttentionManager(self.block_pool, block_size)
        )

        self.req_to_blocks: dict[str, list[KVCacheBlock]] = {}
        # Sliding window: first not-yet-freed block index per request, so
        # each block is nulled exactly once (no O(seq_len) rescans).
        self._first_live_blk: dict[str, int] = {}
        # How many leading blocks of each request are already registered in
        # the prefix cache (avoids re-hashing on every allocate).
        self.num_cached_blocks: dict[str, int] = {}
        # req_id -> token floor above which prefix-cache registration is
        # held back while an external KV load is unconfirmed.
        self.cache_reg_cap: dict[str, int] = {}
        self.prefix_cache_stats = PrefixCacheStats()

    # ------------------------------------------------------------------
    # Prefix cache lookup (waiting -> running transition)
    # ------------------------------------------------------------------

    def get_computed_blocks(self, request: Request) -> tuple[list[KVCacheBlock], int]:
        """Longest cached prefix for a new request.

        Caps the hit at ``num_tokens - 1`` so at least one token is actually
        scheduled (the model must produce logits for sampling) — reference:
        ``find_longest_cache_hit`` semantics in ``kv_cache_utils.py``.
        """
        if not self.enable_caching or not request.block_hashes:
            return [], 0
        max_hit_blocks = (request.num_tokens - 1) // self.block_size
        hit_blocks = self.type_manager.find_longest_cache_hit(
            request, max_hit_blocks
        )
        num_hit_tokens = len(hit_blocks) * self.block_size
        self.prefix_cache_stats.observe(request.num_tokens, num_hit_tokens)
        return hit_blocks, num_hit_tokens

    # ------------------------------------------------------------------
    # Slot allocation (every scheduling of a request)
    # ------------------------------------------------------------------

    def allocate_slots(
        self,
        request: Request,
        num_new_tokens: int,
        new_computed_blocks: list[KVCacheBlock] | None = None,
        num_new_computed_tokens: int = 0,
        num_lookahead_tokens: int = 0,
        num_stored_tokens: int | None = None,
    ) -> list[KVCacheBlock] | None:
        """Ensure the request has blocks covering its tokens after this step.

        ``num_stored_tokens``: how many of the request's tokens hold their
        FINAL K/V once this step has run, where that is not all it runs
        (a denoising pass of generation by diffusion overwrites its block's
        slots again): only pages wholly below it are registered for the
        prefix cache.

        Returns the newly-allocated blocks, or None if the pool cannot
        satisfy the request (caller preempts). Reference:
        ``kv_cache_manager.py allocate_slots``.
        """
        assert num_new_tokens > 0
        new_computed_blocks = new_computed_blocks or []

        req_blocks = self.req_to_blocks.setdefault(request.request_id, [])
        # Reclaim this request's own out-of-window blocks BEFORE the
        # availability check, so a full pool with reclaimable blocks does
        # not spuriously preempt (entries become null stand-ins; list
        # length, and thus the required-block math, is unchanged).
        if self.sliding_window is not None:
            self._free_out_of_window(request, req_blocks)
        num_computed_tokens = request.num_computed_tokens + num_new_computed_tokens
        # Lookahead covers speculative positions whose KV lands this step.
        num_required_blocks = ceil(
            (num_computed_tokens + num_new_tokens + num_lookahead_tokens)
            / self.block_size
        )
        num_new_blocks = (
            num_required_blocks - len(req_blocks) - len(new_computed_blocks)
        )

        # Cache-hit blocks with ref 0 sit in the free queue; touching them
        # consumes free capacity, so subtract them from the availability
        # check (per color: a hit block occupies its own stripe's queue).
        first_color = (
            (len(req_blocks) + len(new_computed_blocks)) % self.num_stripes
        )
        evictable = [0] * self.num_stripes
        for b in new_computed_blocks:
            if b.ref_cnt == 0 and not b.is_null:
                evictable[self.block_pool.color_of(b.block_id)] += 1
        if num_new_blocks > 0 and not self.block_pool.can_allocate(
            num_new_blocks, first_color, evictable
        ):
            return None

        # Commit the cache hits.
        if new_computed_blocks:
            self.block_pool.touch(new_computed_blocks)
            req_blocks.extend(new_computed_blocks)
            self.num_cached_blocks[request.request_id] = len(req_blocks)

        new_blocks: list[KVCacheBlock] = []
        if num_new_blocks > 0:
            new_blocks = self.block_pool.get_new_blocks(
                num_new_blocks, first_color=len(req_blocks) % self.num_stripes
            )
            req_blocks.extend(new_blocks)

        if self.enable_caching:
            self._cache_full_blocks(
                request,
                num_computed_tokens + num_new_tokens
                if num_stored_tokens is None else num_stored_tokens,
            )
        return new_blocks

    def _free_out_of_window(
        self, request: Request, req_blocks: list[KVCacheBlock]
    ) -> None:
        """Per-type freeing policy (SlidingWindowManager nulls blocks
        wholly below the window; full attention frees nothing)."""
        start = self._first_live_blk.get(request.request_id, 0)
        self._first_live_blk[request.request_id] = (
            self.type_manager.remove_skipped_blocks(
                request, req_blocks, start
            )
        )

    def defer_caching_from(self, request_id: str, token_floor: int) -> None:
        """Block prefix-cache registration at/after ``token_floor`` until
        the external KV load covering it is CONFIRMED good.

        A one-shot hold at allocate time is not enough under async lag-1
        scheduling: schedule(N+1)'s allocate catch-up runs before
        update_from_output(N) reports the load outcome, so it would
        register the external span while the failure is still in flight —
        another request admitted in step N+1 could then prefix-hit garbage
        blocks (ADVICE r3 #2). The cap persists until the scheduler calls
        :meth:`confirm_external_load` from update_from_output; the next
        allocate after that catches registration up. Hashes chain, so
        everything from the span start is held back."""
        self.cache_reg_cap[request_id] = token_floor

    def confirm_external_load(self, request_id: str) -> None:
        """The step that performed the external load finalized clean:
        lift the registration cap."""
        self.cache_reg_cap.pop(request_id, None)

    def _cache_full_blocks(self, request: Request, num_tokens_after_step: int) -> None:
        """Register every block that becomes full this step. Speculative
        (unverified) positions are never cached — the caller passes only
        confirmed token counts."""
        cap = self.cache_reg_cap.get(request.request_id)
        if cap is not None:
            num_tokens_after_step = min(num_tokens_after_step, cap)
        num_full = min(
            num_tokens_after_step // self.block_size, len(request.block_hashes)
        )
        num_cached = self.num_cached_blocks.get(request.request_id, 0)
        if num_full <= num_cached:
            return
        self.block_pool.cache_full_blocks(
            self.req_to_blocks[request.request_id],
            request.block_hashes,
            num_cached_blocks=num_cached,
            num_full_blocks=num_full,
        )
        self.num_cached_blocks[request.request_id] = num_full

    # ------------------------------------------------------------------
    # Free
    # ------------------------------------------------------------------

    def invalidate_cached_blocks(self, request: Request) -> None:
        """Drop the request's blocks from the prefix cache (their content
        is garbage after a failed external KV load — a later request, or
        this one's recompute, must not hit them)."""
        for b in self.req_to_blocks.get(request.request_id, []):
            self.block_pool._maybe_evict_cached_block(b)
        self.num_cached_blocks.pop(request.request_id, None)
        self.cache_reg_cap.pop(request.request_id, None)

    def free(self, request: Request) -> None:
        """Release all blocks. Freed tail-first so eviction consumes the end
        of the sequence before its (more reusable) prefix."""
        blocks = self.req_to_blocks.pop(request.request_id, [])
        self.num_cached_blocks.pop(request.request_id, None)
        self.cache_reg_cap.pop(request.request_id, None)
        self._first_live_blk.pop(request.request_id, None)
        self.block_pool.free_blocks(list(reversed(blocks)))

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    def get_block_ids(self, request_id: str) -> list[int]:
        return [b.block_id for b in self.req_to_blocks.get(request_id, [])]

    def get_num_free_blocks(self) -> int:
        return self.block_pool.get_num_free_blocks()

    @property
    def usage(self) -> float:
        return self.block_pool.usage

    def reset_prefix_cache(self) -> bool:
        ok = self.block_pool.reset_prefix_cache()
        if ok:
            self.prefix_cache_stats = PrefixCacheStats()
        return ok
