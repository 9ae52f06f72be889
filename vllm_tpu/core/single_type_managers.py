"""Per-attention-type block management policies.

Reference analog: ``vllm/v1/core/single_type_kv_cache_manager.py``
(FullAttentionManager :xx, SlidingWindowManager :507). The policies —
how a cache-type finds prefix hits and which blocks it may free — are
factored out of :class:`~vllm_tpu.core.kv_cache_manager.KVCacheManager`,
which keeps ONE of these a KV group (``kv_cache_utils.
group_kv_cache_specs``) over the one block pool. Each owns its group's
block table a request: a uniform model has one group and one manager, a
model that mixes window and full layers one full manager and a window
manager for every group of window layers, each freeing what its window
has passed.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from vllm_tpu.core.kv_cache_utils import KVCacheBlock

if TYPE_CHECKING:  # pragma: no cover
    from vllm_tpu.core.block_pool import BlockPool
    from vllm_tpu.request import Request


class FullAttentionManager:
    """Plain causal attention: hits are the longest CONTIGUOUS cached
    prefix; nothing is ever freed early."""

    sliding_window: int | None = None

    def __init__(
        self, block_pool: "BlockPool", block_size: int, group_id: int = 0,
        hash_block_size: int | None = None,
    ) -> None:
        self.block_pool = block_pool
        # Tokens a page of THIS group holds, and how many of the request's
        # content hashes (made a ``hash_block_size`` tokens) one page
        # spans: the page's key is the LAST of them, whose chain covers
        # the page's every token.
        self.block_size = block_size
        self.hashes_per_block = block_size // (hash_block_size or block_size)
        self.group_id = group_id
        # This group's block table a request (null stand-ins where a
        # window manager has given the page back), and how many leading
        # blocks of each are registered in the prefix cache already
        # (avoids re-hashing on every allocate).
        self.req_to_blocks: dict[str, list[KVCacheBlock]] = {}
        self.num_cached_blocks: dict[str, int] = {}
        # Pages of the pool this group's requests hold (null stand-ins are
        # not pages; a page shared through the prefix cache counts once a
        # request): a request, now over all of them, and taken since the
        # start.
        self.pages_held: dict[str, int] = {}
        self.pages_in_use = 0
        self.pages_allocated = 0

    def extend(self, request_id: str, blocks: list[KVCacheBlock]) -> None:
        """Append blocks taken from the pool (new, or cache hits touched)
        to the request's table."""
        self.req_to_blocks[request_id].extend(blocks)
        n = sum(not b.is_null for b in blocks)
        self.pages_held[request_id] = self.pages_held.get(request_id, 0) + n
        self.pages_in_use += n
        self.pages_allocated += n

    def _page_hashes(self, request: "Request") -> list:
        """The request's content hashes, one a page of this group."""
        k = self.hashes_per_block
        if k == 1:
            return request.block_hashes
        return request.block_hashes[k - 1::k]

    def find_longest_cache_hit(
        self, request: "Request", max_hit_blocks: int
    ) -> list[KVCacheBlock]:
        hit: list[KVCacheBlock] = []
        for block_hash in self._page_hashes(request)[:max_hit_blocks]:
            block = self.block_pool.get_cached_block(
                block_hash, self.group_id)
            if block is None:
                break
            hit.append(block)
        return hit

    def cache_full_blocks(self, request: "Request", num_hashed: int) -> None:
        """Register the request's pages that the first ``num_hashed``
        content hashes fill and that are not registered yet, under their
        hashes and this group."""
        num_full = num_hashed // self.hashes_per_block
        num_cached = self.num_cached_blocks.get(request.request_id, 0)
        if num_full <= num_cached:
            return
        self.block_pool.cache_full_blocks(
            self.req_to_blocks[request.request_id],
            self._page_hashes(request),
            num_cached_blocks=num_cached,
            num_full_blocks=num_full,
            group_id=self.group_id,
        )
        self.num_cached_blocks[request.request_id] = num_full

    def free(self, request_id: str) -> None:
        """Release the request's blocks of this group, tail first so that
        eviction consumes the end of the sequence before its (more
        reusable) prefix."""
        blocks = self.req_to_blocks.pop(request_id, [])
        self.num_cached_blocks.pop(request_id, None)
        self.pages_in_use -= self.pages_held.pop(request_id, 0)
        self.block_pool.free_blocks(list(reversed(blocks)))


class SlidingWindowManager(FullAttentionManager):
    """Sliding-window attention: hits are the LAST cached run covering
    the window (out-of-window prefix served as null stand-ins), and
    blocks wholly below the window are freed as the sequence advances.
    Reference: ``single_type_kv_cache_manager.py:507``."""

    def __init__(
        self, block_pool: "BlockPool", block_size: int, sliding_window: int,
        group_id: int = 0, hash_block_size: int | None = None,
    ) -> None:
        super().__init__(block_pool, block_size, group_id, hash_block_size)
        self.sliding_window = sliding_window
        # First not-yet-freed block index a request, so each block is
        # nulled exactly once (no O(seq_len) rescans).
        self._first_live_blk: dict[str, int] = {}

    def find_longest_cache_hit(
        self, request: "Request", max_hit_blocks: int
    ) -> list[KVCacheBlock]:
        """The first scheduled query (position P = hit tokens) only
        attends keys in ``(P - window, P)``: a hit needs a contiguous
        cached RUN of ``ceil((window-1)/bs)`` blocks ending at P. Scan
        backward for the LAST such run; a run anchored at block 0 is a
        plain prefix hit at any length."""
        required = -(-(self.sliding_window - 1) // self.block_size)
        hashes = self._page_hashes(request)[:max_hit_blocks]
        null = self.block_pool.null_block
        blocks: list[KVCacheBlock] = [null] * len(hashes)
        run = 0
        for i in range(len(hashes) - 1, -1, -1):
            block = self.block_pool.get_cached_block(
                hashes[i], self.group_id)
            if block is None:
                run = 0
                continue
            blocks[i] = block
            run += 1
            if run >= required:
                return blocks[: i + run]
        # Loop exhausted: the only usable run is the one anchored at
        # block 0 (plain prefix semantics).
        return blocks[:run]

    def remove_skipped_blocks(self, request: "Request") -> int:
        """Replace blocks wholly below the window with the null block and
        free them; returns how many pages went back to the pool. Entries
        stay in the table (reads are window-masked, slots never
        rewritten). The floor uses only ROLLBACK-PROOF tokens (async
        scheduling advances counts optimistically; spec verification can
        roll back)."""
        req_blocks = self.req_to_blocks[request.request_id]
        first_live = self._first_live_blk.get(request.request_id, 0)
        confirmed = (
            request.num_computed_tokens
            - request.num_output_placeholders
            - len(request.spec_token_ids)
        )
        first_needed_tok = max(0, confirmed - self.sliding_window + 1)
        first_needed_blk = min(
            first_needed_tok // self.block_size, len(req_blocks)
        )
        null = self.block_pool.null_block
        freed = 0
        for i in range(first_live, first_needed_blk):
            b = req_blocks[i]
            if b.is_null:
                continue
            req_blocks[i] = null
            self.block_pool.free_blocks([b])
            freed += 1
        self._first_live_blk[request.request_id] = max(
            first_live, first_needed_blk)
        if freed:
            self.pages_held[request.request_id] -= freed
            self.pages_in_use -= freed
        return freed

    def free(self, request_id: str) -> None:
        self._first_live_blk.pop(request_id, None)
        super().free(request_id)
