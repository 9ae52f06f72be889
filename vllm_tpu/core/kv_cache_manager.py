"""Request-level KV block allocation with prefix-cache reuse.

Reference analog: ``vllm/v1/core/kv_cache_manager.py:106`` with the
coordinators of ``kv_cache_coordinator.py`` folded in: the manager keeps a
list of single-type managers, ONE A KV GROUP (``kv_cache_utils.
group_kv_cache_specs``), over one ``BlockPool``. A uniform model is a list
of one, and every count below is that manager's; a model that mixes window
and full layers has a block table a group and request, needs the SUM of the
groups' new blocks from the pool, and its window groups hand the pages
their window has passed back to it as the request advances. A page holds
``block_size`` tokens of ITS group: groups whose layers cost unlike bytes a
token hold unlike tokens a page (``KVCacheGroupSpec.block_size``), so every
count of pages below is taken a group, by that group's manager, and the
manager's own ``block_size`` is only the smallest of them, the one the
request's hashes are made at. Recurrent-state slots are not groups: they
stay with the runner (ROADMAP D3).
"""

from __future__ import annotations

from dataclasses import dataclass
from math import ceil

from vllm_tpu.core.block_pool import BlockPool
from vllm_tpu.core.kv_cache_utils import KVCacheBlock, KVCacheGroupSpec
from vllm_tpu.core.single_type_managers import (
    FullAttentionManager,
    SlidingWindowManager,
)
from vllm_tpu.logger import init_logger
from vllm_tpu.request import Request
from vllm_tpu.tracing import trace_span

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
        kv_cache_groups: list[KVCacheGroupSpec] | None = None,
    ) -> None:
        # The engine's: the tokens a content hash of a request covers, and
        # the smallest of the groups' (a group's is a multiple of it).
        self.block_size = block_size
        # Context parallelism: a request's k-th context block comes from
        # pool color k % num_stripes (= the cp rank holding that page).
        self.num_stripes = num_stripes
        self.enable_caching = enable_caching
        self.block_pool = BlockPool(
            num_blocks, enable_caching,
            event_sink=event_sink, block_size=block_size,
            num_colors=num_stripes,
        )
        # One policy a group (full attention; sliding window: window-aware
        # prefix hits + out-of-window freeing). Without the worker's
        # groups: one group, a window group where ``sliding_window`` says
        # so.
        windows = (
            [sliding_window] if kv_cache_groups is None
            else [g.sliding_window for g in kv_cache_groups]
        )
        sizes = (
            [block_size] if kv_cache_groups is None
            else [g.block_size for g in kv_cache_groups]
        )
        if len(windows) > 1 and num_stripes > 1:
            raise NotImplementedError(
                "context parallelism with several KV groups: the pool's "
                "stripes follow ONE block table's order"
            )
        if any(size % block_size for size in sizes):
            raise ValueError(
                f"a KV group's page must hold a multiple of the {block_size} "
                f"tokens a content hash covers; got {sizes}")
        self.managers = [
            FullAttentionManager(
                self.block_pool, size, g, hash_block_size=block_size)
            if window is None
            else SlidingWindowManager(
                self.block_pool, size, window, g, hash_block_size=block_size)
            for g, (window, size) in enumerate(zip(windows, sizes))
        ]
        self.num_groups = len(self.managers)
        # A prefix hit ends where every group's pages end: a multiple of
        # the largest page's tokens (each a multiple of the hashes').
        self._hit_unit = max(sizes)
        # What a lookup that hit nothing and an allocation that took
        # nothing return: a list a group, as every block table here is.
        self._no_blocks: tuple[list[KVCacheBlock], ...] = tuple(
            [] for _ in self.managers)
        self._window_managers = [
            m for m in self.managers if m.sliding_window is not None
        ]
        # Group 0's tables and registered counts under the names they had
        # when there was one group (the scheduler's external-load path and
        # the tests read them).
        self.req_to_blocks = self.managers[0].req_to_blocks
        self.num_cached_blocks = self.managers[0].num_cached_blocks
        # req_id -> token floor above which prefix-cache registration is
        # held back while an external KV load is unconfirmed.
        self.cache_reg_cap: dict[str, int] = {}
        self.prefix_cache_stats = PrefixCacheStats()
        # Cumulative: pages the window groups gave back before their
        # request ended (the managers count what each group took).
        self.window_pages_freed = 0

    # ------------------------------------------------------------------
    # Prefix cache lookup (waiting -> running transition)
    # ------------------------------------------------------------------

    def get_computed_blocks(
        self, request: Request
    ) -> tuple[tuple[list[KVCacheBlock], ...], int]:
        """Longest cached prefix for a new request: the hit blocks, a list
        a group, and the hit's tokens.

        Caps the hit at ``num_tokens - 1`` so at least one token is actually
        scheduled (the model must produce logits for sampling) — reference:
        ``find_longest_cache_hit`` semantics in ``kv_cache_utils.py``. With
        several groups the hit is the longest length at which EVERY group
        holds what its layers will read there (a full group the contiguous
        prefix, a window group the last run that covers the window): each
        group is asked under the others' answer until all give the same
        (reference: ``HybridKVCacheCoordinator.find_longest_cache_hit``).
        """
        if not self.enable_caching or not request.block_hashes:
            return self._no_blocks, 0
        unit = self._hit_unit
        max_hit_tokens = (request.num_tokens - 1) // unit * unit
        while True:
            hits = [
                m.find_longest_cache_hit(
                    request, max_hit_tokens // m.block_size)
                for m in self.managers
            ]
            lengths = [len(h) * m.block_size
                       for h, m in zip(hits, self.managers)]
            shortest = min(lengths) // unit * unit
            if all(n == shortest for n in lengths):
                break
            max_hit_tokens = shortest
        self.prefix_cache_stats.observe(request.num_tokens, shortest)
        return (tuple(hits) if shortest else self._no_blocks), shortest

    # ------------------------------------------------------------------
    # Slot allocation (every scheduling of a request)
    # ------------------------------------------------------------------

    def allocate_slots(
        self,
        request: Request,
        num_new_tokens: int,
        new_computed_blocks: tuple[list[KVCacheBlock], ...] | None = None,
        num_new_computed_tokens: int = 0,
        num_lookahead_tokens: int = 0,
        num_stored_tokens: int | None = None,
    ) -> tuple[list[KVCacheBlock], ...] | None:
        """Ensure the request has blocks covering its tokens after this step
        in every group.

        ``new_computed_blocks``: what ``get_computed_blocks`` returned.
        ``num_stored_tokens``: how many of the request's tokens hold their
        FINAL K/V once this step has run, where that is not all it runs (a
        denoising pass of generation by diffusion overwrites its block's
        slots again): only pages wholly below it are registered for the
        prefix cache.

        Returns the newly-allocated blocks, a list a group, or None if the
        pool cannot satisfy the request (caller preempts). Reference:
        ``kv_cache_manager.py allocate_slots``.
        """
        assert num_new_tokens > 0
        rid = request.request_id
        tables = [m.req_to_blocks.setdefault(rid, []) for m in self.managers]
        # Reclaim this request's own out-of-window blocks BEFORE the
        # availability check, so a full pool with reclaimable blocks does
        # not spuriously preempt (entries become null stand-ins; list
        # length, and thus the required-block math, is unchanged).
        if self._window_managers:
            with trace_span("free_window_pages", "scheduler"):
                for m in self._window_managers:
                    self.window_pages_freed += m.remove_skipped_blocks(request)
        num_computed_tokens = request.num_computed_tokens + num_new_computed_tokens
        # Lookahead covers speculative positions whose KV lands this step.
        num_tokens_after = (
            num_computed_tokens + num_new_tokens + num_lookahead_tokens)
        stored = (
            num_computed_tokens + num_new_tokens
            if num_stored_tokens is None else num_stored_tokens
        )
        # A group's table has one entry a page of ITS tokens over the
        # sequence (a null stand-in where the page went back): groups of
        # one page size need the same count, a group of twice the tokens
        # a page half of it.
        hits = new_computed_blocks or self._no_blocks
        num_new_blocks = [
            ceil(num_tokens_after / m.block_size) - len(table) - len(hit)
            for m, table, hit in zip(self.managers, tables, hits)
        ]
        if not hits[0] and max(num_new_blocks) <= 0:
            # Most calls: a decode row whose last block has room.
            if self.enable_caching:
                self._cache_full_blocks(request, stored)
            return self._no_blocks

        # Cache-hit blocks with ref 0 sit in the free queue; touching them
        # consumes free capacity, so subtract them from the availability
        # check (per color: a hit block occupies its own stripe's queue).
        first_color = (len(tables[0]) + len(hits[0])) % self.num_stripes
        evictable = [0] * self.num_stripes
        for hit in hits:
            for b in hit:
                if b.ref_cnt == 0 and not b.is_null:
                    evictable[self.block_pool.color_of(b.block_id)] += 1
        needed = sum(n for n in num_new_blocks if n > 0)
        if needed and not self.block_pool.can_allocate(
            needed, first_color, evictable
        ):
            return None

        new_blocks: list[list[KVCacheBlock]] = []
        for m, table, hit, n in zip(
                self.managers, tables, hits, num_new_blocks):
            # Commit the cache hits.
            if hit:
                self.block_pool.touch(hit)
                m.extend(rid, hit)
                m.num_cached_blocks[rid] = len(table)
            new = []
            if n > 0:
                new = self.block_pool.get_new_blocks(
                    n, first_color=len(table) % self.num_stripes
                )
                m.extend(rid, new)
            new_blocks.append(new)

        if self.enable_caching:
            self._cache_full_blocks(request, stored)
        return tuple(new_blocks)

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
        num_hashed = min(
            num_tokens_after_step // self.block_size, len(request.block_hashes)
        )
        for m in self.managers:
            m.cache_full_blocks(request, num_hashed)

    # ------------------------------------------------------------------
    # Free
    # ------------------------------------------------------------------

    def invalidate_cached_blocks(self, request: Request) -> None:
        """Drop the request's blocks from the prefix cache (their content
        is garbage after a failed external KV load — a later request, or
        this one's recompute, must not hit them)."""
        for m in self.managers:
            for b in m.req_to_blocks.get(request.request_id, []):
                self.block_pool._maybe_evict_cached_block(b)
            m.num_cached_blocks.pop(request.request_id, None)
        self.cache_reg_cap.pop(request.request_id, None)

    def free(self, request: Request) -> None:
        """Release the request's blocks of every group."""
        self.cache_reg_cap.pop(request.request_id, None)
        for m in self.managers:
            m.free(request.request_id)

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    def get_block_ids(self, request_id: str) -> tuple[list[int], ...]:
        """The request's block table, a list a group."""
        return tuple(
            [b.block_id for b in m.req_to_blocks.get(request_id, [])]
            for m in self.managers
        )

    def num_pages_held(self, request_id: str) -> int:
        """Pages of the pool the request holds over all groups (a null
        stand-in where a window has passed is not a page)."""
        held = 0
        for m in self.managers:  # every request's every step: no generator
            held += m.pages_held.get(request_id, 0)
        return held

    def pages_in_use_by_group(self) -> list[int]:
        return [m.pages_in_use for m in self.managers]

    def pages_allocated_by_group(self) -> list[int]:
        return [m.pages_allocated for m in self.managers]

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
