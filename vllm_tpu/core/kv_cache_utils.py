"""KV-cache block bookkeeping primitives.

Reference analog: ``vllm/v1/core/kv_cache_utils.py`` — content-addressed
block hashing for the prefix cache, the free-block queue with O(1) removal,
and KV-cache sizing helpers. All host-side, device-agnostic.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, NamedTuple, Optional

if TYPE_CHECKING:
    from vllm_tpu.request import Request

# A block hash is the digest of (parent_hash, tokens_in_block[, extra]).
# bytes keeps it stable across processes (unlike builtin hash()).
BlockHash = bytes


class BlockHashWithGroupId(NamedTuple):
    """Prefix-cache key: hash is per-content, group disambiguates KV groups
    (hybrid models cache full-attention and sliding-window layers
    separately)."""

    block_hash: BlockHash
    group_id: int


# Root of every hash chain. Distinct from any real digest.
NONE_HASH: BlockHash = b"\x00" * 16


def hash_block_tokens(
    parent_hash: BlockHash,
    token_ids: "list[int] | tuple[int, ...]",
    extra_keys: tuple | None = None,
) -> BlockHash:
    """Chain-hash one full block of tokens onto its parent.

    Reference: ``kv_cache_utils.py hash_block_tokens``. The chain makes a
    block's identity cover its entire prefix, so a dict lookup is a full
    prefix match.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(parent_hash)
    h.update(struct.pack(f"<{len(token_ids)}q", *token_ids))
    if extra_keys:
        h.update(repr(extra_keys).encode())
    return h.digest()


def make_block_hasher(block_size: int) -> Callable[["Request"], list[BlockHash]]:
    """Return an incremental hasher: called after tokens append, it returns
    hashes for any newly-completed full blocks past ``request.block_hashes``.

    Reference: ``kv_cache_utils.py get_request_block_hasher``.
    """

    def hasher(request: "Request") -> list[BlockHash]:
        start = len(request.block_hashes)
        prev = request.block_hashes[-1] if request.block_hashes else NONE_HASH
        tokens = request.all_token_ids
        num_full = len(tokens) // block_size
        out: list[BlockHash] = []
        extra = _request_extra_keys(request)
        for i in range(start, num_full):
            prev = hash_block_tokens(
                prev, tokens[i * block_size : (i + 1) * block_size], extra
            )
            out.append(prev)
        return out

    return hasher


def _request_extra_keys(request: "Request") -> tuple | None:
    """Keys that change KV content beyond token ids (LoRA adapter, mm
    hashes). Reference: ``generate_block_hash_extra_keys``."""
    if request.lora_name is not None:
        return (request.lora_name,)
    return None


@dataclass
class KVCacheBlock:
    """One physical block's bookkeeping entry.

    Reference: ``kv_cache_utils.py:114``. Doubly-linked free-list pointers
    live inline so eviction-order removal is O(1).
    """

    block_id: int
    ref_cnt: int = 0
    block_hash: Optional[BlockHashWithGroupId] = None
    prev_free_block: Optional["KVCacheBlock"] = None
    next_free_block: Optional["KVCacheBlock"] = None
    # True only for the null block (block 0, permanent placeholder).
    is_null: bool = False

    def incr_ref(self) -> None:
        self.ref_cnt += 1

    def decr_ref(self) -> None:
        self.ref_cnt -= 1

    def reset_hash(self) -> None:
        self.block_hash = None

    def __repr__(self) -> str:
        return f"KVCacheBlock(id={self.block_id}, ref={self.ref_cnt})"


class FreeKVCacheBlockQueue:
    """Doubly-linked LRU free list with O(1) append/popleft/remove.

    Blocks are freed in reverse-request order so that the *tail* blocks of a
    freed sequence are evicted before its head — preserving long prefixes in
    the cache as long as possible (reference: ``FreeKVCacheBlockQueue``
    docstring, ``kv_cache_utils.py:162``).
    """

    def __init__(self, blocks: list[KVCacheBlock]) -> None:
        self.num_free_blocks = len(blocks)
        # Sentinel head/tail keep edge cases out of the hot path.
        self._head = KVCacheBlock(block_id=-1)
        self._tail = KVCacheBlock(block_id=-2)
        self._head.next_free_block = self._tail
        self._tail.prev_free_block = self._head
        for b in blocks:
            self.append(b)
        self.num_free_blocks = len(blocks)

    def popleft(self) -> KVCacheBlock:
        block = self._head.next_free_block
        assert block is not None and block is not self._tail, "free queue is empty"
        self.remove(block)
        return block

    def remove(self, block: KVCacheBlock) -> None:
        prev, nxt = block.prev_free_block, block.next_free_block
        assert prev is not None and nxt is not None, (
            f"block {block.block_id} is not in the free queue"
        )
        prev.next_free_block = nxt
        nxt.prev_free_block = prev
        block.prev_free_block = block.next_free_block = None
        self.num_free_blocks -= 1

    def append(self, block: KVCacheBlock) -> None:
        last = self._tail.prev_free_block
        assert last is not None
        last.next_free_block = block
        block.prev_free_block = last
        block.next_free_block = self._tail
        self._tail.prev_free_block = block
        self.num_free_blocks += 1

    def get_all_free_blocks(self) -> list[KVCacheBlock]:
        out = []
        cur = self._head.next_free_block
        while cur is not self._tail:
            assert cur is not None
            out.append(cur)
            cur = cur.next_free_block
        return out


def _lane_padded(n: int) -> int:
    """Physical lane width of a minor array dim on TPU.

    XLA tiles the minor dim to 128 lanes, so ``f32[..., 2, 32]`` occupies
    ``(2, 128)`` tiles — 4x the logical bytes. Sizing must budget physical
    bytes or the computed block count OOMs at allocation time (observed
    with small head_dim models on v5e).
    """
    import jax

    if jax.default_backend() != "tpu":
        return n
    return -(-n // 128) * 128


@dataclass
class KVCacheSpec:
    """Per-layer cache requirement (reference: ``vllm/v1/kv_cache_interface.py``).

    ``page_size_bytes`` drives KV sizing; the worker allocates
    ``num_blocks`` pages per layer. ``v_head_size`` is the value head's
    width where it is not the key's (None: ``head_size`` for both, the
    layouts every model had).
    """

    block_size: int
    num_kv_heads: int
    head_size: int
    dtype_bytes: int
    v_head_size: int | None = field(default=None, kw_only=True)

    @property
    def page_size_bytes(self) -> int:
        # Mirrors ops/attention.py kv_cache_shape: head_dim below the
        # 128-lane tile pair-packs K||V on the lane axis ([.., KH, 2*D]);
        # otherwise K/V interleave on the sublane axis ([.., 2*KH, D]);
        # unlike key and value widths give head-major rows of a key padded
        # to whole lane tiles and its value ([.., KH, BS, Dk_pad + Dv]).
        # Budget the lane-padded physical bytes of the actual minor dim.
        # The second-minor dim is not modeled: it is whole sublane tiles
        # in the first two layouts at every KV-head count served so far
        # (16 rows of bf16), and ``block_size`` tokens in the third, which
        # ``ops/attention.py::split_kv_cache_shape`` refuses where they
        # would pad; the runner compares the allocation with what the
        # device reports (``ModelRunner._alloc_kv_cache`` logs "KV cache
        # on the device", a warning past 2%).
        from vllm_tpu.ops.attention import packed_kv_layout, split_kv_lanes

        if self.v_head_size not in (None, self.head_size):
            rows = self.num_kv_heads
            lanes = split_kv_lanes(self.head_size, self.v_head_size)
        elif packed_kv_layout(self.head_size):
            rows, lanes = self.num_kv_heads, 2 * self.head_size
        else:
            rows, lanes = 2 * self.num_kv_heads, self.head_size
        return (
            self.block_size * rows * _lane_padded(lanes) * self.dtype_bytes
        )

    @property
    def published_token_bytes(self) -> int:
        """Bytes a token a layer as the model's config states them: a key
        and a value a KV head, no padding."""
        v = self.head_size if self.v_head_size is None else self.v_head_size
        return self.num_kv_heads * (self.head_size + v) * self.dtype_bytes

    def max_memory_usage_bytes(self, max_model_len: int) -> int:
        import math

        return math.ceil(max_model_len / self.block_size) * self.page_size_bytes


@dataclass
class FullAttentionSpec(KVCacheSpec):
    sliding_window: int | None = None


@dataclass
class MLAAttentionSpec(KVCacheSpec):
    """Latent-compressed cache (reference: ``kv_cache_interface.py:323``):
    ONE latent row per token (c_kv || k_pe) shared by all heads — no K/V
    planes. ``head_size`` is the latent width (kv_lora_rank + rope dim)."""

    @property
    def page_size_bytes(self) -> int:
        # The cache pads a row to whole 128-lane tiles on every backend
        # (``ops/mla_attention.py::mla_kv_cache_shape``).
        return (
            self.block_size * self.num_kv_heads
            * (-(-self.head_size // 128) * 128) * self.dtype_bytes
        )


@dataclass
class SlidingWindowSpec(KVCacheSpec):
    sliding_window: int = 4096

    def max_memory_usage_bytes(self, max_model_len: int) -> int:
        import math

        window = min(self.sliding_window, max_model_len)
        # +1 block: the window straddles block boundaries.
        return (math.ceil(window / self.block_size) + 1) * self.page_size_bytes


@dataclass
class MambaSpec(KVCacheSpec):
    """SSM state: one fixed-size page per request, block_size = max_model_len
    so the whole state is a single 'block'."""

    state_shape: tuple = ()

    @property
    def page_size_bytes(self) -> int:
        n = 1
        for d in self.state_shape:
            n *= d
        return n * self.dtype_bytes


@dataclass
class KVCacheGroupSpec:
    """Layers sharing one block-table/allocation group: a page id of the
    group holds ``block_size`` tokens of EVERY layer named here.
    ``num_places`` is the layers a page id of the pool has room for (the
    cache array's first axis): more than the group's layers only in a short
    last group, whose places past its layers are never written."""

    layer_names: list[str]
    kv_cache_spec: KVCacheSpec
    num_places: int = 0

    def __post_init__(self) -> None:
        self.num_places = self.num_places or len(self.layer_names)

    @property
    def block_size(self) -> int:
        """Tokens a page of THIS group holds; groups of one pool may
        differ in it (never in a page's bytes)."""
        return self.kv_cache_spec.block_size

    @property
    def page_size_bytes(self) -> int:
        return self.num_places * self.kv_cache_spec.page_size_bytes

    @property
    def lost_bytes_per_page(self) -> int:
        """Bytes of a page no layer of the group writes (a short group)."""
        return (self.num_places - len(self.layer_names)) * (
            self.kv_cache_spec.page_size_bytes)

    @property
    def sliding_window(self) -> int | None:
        """The window the group's pages may be freed under (None: every
        page is kept while the request lives)."""
        if isinstance(self.kv_cache_spec, SlidingWindowSpec):
            return self.kv_cache_spec.sliding_window
        return None


def group_kv_cache_specs(
    specs: dict[str, KVCacheSpec],
) -> list[KVCacheGroupSpec]:
    """Group a model's paged layers by spec into groups of EQUAL page bytes
    over one page pool (reference: ``kv_cache_utils.py
    _get_kv_cache_groups_uniform_page_size``).

    One spec gives one group of every layer: the page, the block count and
    the step programs a uniform model always had. Several specs (window
    layers beside full ones) give groups of the smallest kind's layer
    count: a kind with ``k`` times as many layers gives ``k`` groups, its
    ``i``-th layer going to the kind's group ``i % k`` (layers 1, 2, 3, 5,
    6, 7, ... of a 1:3 pattern are groups 1, 2 and 3 with one layer of each
    a period). Where a kind's count is no multiple, its groups are filled
    in layer order and the LAST one is short: its page ids still pay for
    the whole group size (``KVCacheGroupSpec.lost_bytes_per_page``).

    Kinds whose layers cost unlike bytes a token (a kind with half the KV
    heads) keep one page SIZE by unlike tokens a page: the kind with the
    most bytes a token keeps the specs' ``block_size``, a kind with ``1/m``
    of them holds ``m`` times the tokens, and a ratio that is no whole
    number is refused. A page id then means ``group.block_size`` tokens of
    one group's layers, whichever group it was handed to, and the cache is
    one array ``[group size, pages, ...]`` indexed by a layer's place in
    its group.
    """
    assert specs, "model exposed no KV cache specs"
    import dataclasses

    kinds: dict[tuple, KVCacheGroupSpec] = {}
    for name, spec in specs.items():
        key = (type(spec).__name__,) + dataclasses.astuple(spec)
        if key not in kinds:
            kinds[key] = KVCacheGroupSpec([], spec)
        kinds[key].layer_names.append(name)
    if len(kinds) == 1:
        return [KVCacheGroupSpec(k.layer_names, k.kv_cache_spec)
                for k in kinds.values()]
    page = max(k.kv_cache_spec.page_size_bytes for k in kinds.values())
    uneven = [k for k in kinds.values()
              if page % k.kv_cache_spec.page_size_bytes]
    if uneven:
        raise ValueError(
            "KV groups over one page pool need pages of equal bytes: a "
            "kind's bytes a token must divide the largest kind's; got "
            + ", ".join(
                f"{len(k.layer_names)} x {type(k.kv_cache_spec).__name__} "
                f"of {k.kv_cache_spec.page_size_bytes} bytes a "
                f"{k.kv_cache_spec.block_size} tokens"
                for k in kinds.values())
        )
    size = min(len(k.layer_names) for k in kinds.values())
    groups = []
    for kind in kinds.values():
        more = page // kind.kv_cache_spec.page_size_bytes
        spec = kind.kv_cache_spec if more == 1 else dataclasses.replace(
            kind.kv_cache_spec, block_size=more * kind.kv_cache_spec.block_size)
        names = kind.layer_names
        k = -(-len(names) // size)
        groups += [
            KVCacheGroupSpec(
                names[j * size:(j + 1) * size] if len(names) % size
                else names[j::k],
                spec, num_places=size)
            for j in range(k)
        ]
    return groups
