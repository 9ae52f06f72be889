"""The repo's grouped matmul (``ops/gmm_kernel.py``) against megablox
``gmm``, which it forks: the same rows in the same groups, float32 out, bit
for bit in interpret mode; its walk against megablox's own metadata; the
host's count of visits; and the road ``layers/moe.py`` takes to it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu import megablox
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from vllm_tpu.layers import moe
from vllm_tpu.ops import gmm_kernel


def operands(rng, m, g, k, n, dtype):
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)) * 0.1, dtype)
    return lhs, rhs


def megablox_gmm(lhs, rhs, sizes, tn, tk=None):
    return megablox.gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.float32,
        tiling=(128, tk or lhs.shape[1], tn), interpret=True)


# name -> (group sizes, rows m, k, n, column tile)
WALKS = {
    # 100 | 28 + 128 + 128 + 16 | 50 ... : one group over four row tiles.
    "straddles_several_tiles": ([100, 300, 50, 62], 512, 128, 128, 128),
    # Every boundary inside a tile: each group but the first is visited in
    # the tile the one before it ends in.
    "straddles_one_tile": ([60, 130, 70, 100, 24], 384, 128, 128, 128),
    "empty_between_full": ([0, 128, 0, 0, 256, 0, 128, 0], 512, 128, 128, 128),
    "rows_past_the_groups": ([40, 0, 90], 640, 128, 128, 128),
    "one_row_a_group": ([1] * 9, 128, 128, 128, 128),
    "two_column_tiles": ([100, 0, 300, 50, 0, 62], 640, 128, 256, 128),
    "four_column_tiles": ([100, 0, 300, 50, 0, 62], 640, 256, 512, 128),
    "one_group_whole": ([384], 384, 128, 256, 256),
    # Three depth tiles of 128 (kimi-linear's gate is two of 1152).
    "depth_in_three": ([100, 0, 300, 50, 0, 62], 640, 384, 256, 128),
    # A [L * E] stack of 3 layers of 4 experts, the second layer's filled.
    "a_layer_of_a_stack": ([0] * 4 + [70, 0, 200, 30] + [0] * 4, 384, 128, 256, 128),
}


@pytest.mark.parametrize("slots", [1, 2, 3, 4])
@pytest.mark.parametrize("walk", WALKS)
def test_bit_equal_to_megablox(walk, slots):
    sizes, m, k, n, tn = WALKS[walk]
    tk = 128 if walk == "depth_in_three" else k
    rng = np.random.default_rng(len(walk))
    lhs, rhs = operands(rng, m, len(sizes), k, n, jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    want = np.asarray(megablox_gmm(lhs, rhs, sizes, tn, tk))[:live]
    got = np.asarray(gmm_kernel.gmm(
        lhs, rhs, sizes, tk=tk, tn=tn, slots=slots, interpret=True))
    assert got.dtype == np.float32 and got.shape == (m, n)
    np.testing.assert_array_equal(got[:live], want)
    assert np.abs(want).max() > 0.1


def test_float32_operands_are_multiplied_in_float32():
    rng = np.random.default_rng(3)
    lhs, rhs = operands(rng, 256, 3, 128, 256, jnp.float32)
    sizes = jnp.asarray([90, 0, 120], jnp.int32)
    want = np.asarray(megablox_gmm(lhs, rhs, sizes, 128))[:210]
    got = np.asarray(gmm_kernel.gmm(lhs, rhs, sizes, tn=128, interpret=True))
    np.testing.assert_array_equal(got[:210], want)
    rows = np.asarray(lhs)[:90] @ np.asarray(rhs)[0]
    np.testing.assert_allclose(got[:90], rows, rtol=1e-5, atol=1e-5)


def test_no_group_no_visit():
    """Nothing to run: the grid is empty, no copy is started, none is
    waited for."""
    lhs, rhs = operands(np.random.default_rng(0), 128, 3, 128, 128, jnp.bfloat16)
    out = gmm_kernel.gmm(
        lhs, rhs, jnp.zeros(3, jnp.int32), tn=128, interpret=True)
    assert out.shape == (128, 128)


@pytest.mark.parametrize("bad", ["rows", "depth", "depth_tile", "column_tile", "sizes"])
def test_what_the_kernel_refuses(bad):
    lhs, rhs = operands(np.random.default_rng(0), 128, 2, 128, 256, jnp.bfloat16)
    sizes, tn, tk = jnp.asarray([5, 5], jnp.int32), 128, None
    if bad == "rows":
        lhs = lhs[:100]
    elif bad == "depth":
        lhs, rhs = lhs[:, :64], rhs[:, :64]
    elif bad == "depth_tile":
        tk = 96
    elif bad == "column_tile":
        tn = 192
    else:
        sizes = sizes.astype(jnp.float32)
    with pytest.raises(ValueError):
        gmm_kernel.gmm(lhs, rhs, sizes, tk=tk, tn=tn, interpret=True)


SIZES = {
    "straddles": ([100, 300, 50, 62], 512),
    "aligned": ([128, 256, 128], 512),
    "empties": ([0, 7, 0, 0, 300, 0], 384),
    "short": ([40, 0, 90], 640),
    "ones": ([1] * 9, 128),
    "tile_ends": ([128, 1, 127, 129, 127], 512),
}


@pytest.mark.parametrize("name", SIZES)
def test_the_walk_is_megabloxs_own(name):
    """The same (group, row tile) pairs in the same order as megablox's
    metadata gives, the groups that hold rows numbered as blocks."""
    sizes, m = SIZES[name]
    gs = jnp.asarray(sizes, jnp.int32)
    (_, group_ids, m_tile_ids), n = make_group_metadata(
        group_sizes=gs, m=m, tm=128, start_group=jnp.int32(0),
        num_nonzero_groups=len(sizes), visit_empty_groups=False)
    offsets, block_group, visit_block, visit_tile, counts = map(
        np.asarray, gmm_kernel.visit_metadata(gs, m))
    n = int(n)
    hit = np.flatnonzero(sizes)
    assert list(counts) == [len(hit), n]
    np.testing.assert_array_equal(block_group[:len(hit)], hit)
    np.testing.assert_array_equal(
        block_group[visit_block[:n]], np.asarray(group_ids)[:n])
    np.testing.assert_array_equal(visit_tile[:n], np.asarray(m_tile_ids)[:n])
    np.testing.assert_array_equal(
        offsets[:len(hit) + 1], np.concatenate([[0], np.cumsum(np.asarray(sizes)[hit])]))
    assert visit_tile.max() < m // 128 and len(visit_block) == m // 128 + len(sizes) - 1


@pytest.mark.parametrize("name", SIZES)
@pytest.mark.parametrize("column_tiles", [1, 2, 4])
def test_the_hosts_count_of_visits(name, column_tiles):
    """first + resident = megablox's own count of tiles to run, a column
    tile; first = the groups that hold rows."""
    sizes, m = SIZES[name]
    _, n = make_group_metadata(
        group_sizes=jnp.asarray(sizes, jnp.int32), m=m, tm=128,
        start_group=jnp.int32(0), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    first, resident = gmm_kernel.count_visits(sizes, column_tiles)
    assert first == np.count_nonzero(sizes) * column_tiles
    assert first + resident == int(n) * column_tiles
    # A layer a row, as the runner hands them over: the rows add up.
    stacked = np.stack([sizes, sizes[::-1], np.zeros_like(sizes)])
    back = gmm_kernel.count_visits(sizes[::-1], column_tiles)
    assert gmm_kernel.count_visits(stacked, column_tiles) == (
        first + back[0], resident + back[1])


def test_a_full_mixed_step_of_lfm2_by_the_count():
    """32 groups over 2,048 rows, no boundary on a tile's edge: 16 row
    tiles + 32 groups - 1 = 47 visits a column tile, 15 of them resident
    (the issue's arithmetic); whole tiles a group, none."""
    sizes = np.full(32, 64)
    sizes[0], sizes[-1] = 32, 96  # a boundary every 64 rows from row 32 on
    assert gmm_kernel.count_visits(sizes, 1) == (32, 15)
    assert gmm_kernel.count_visits(np.full(32, 128), 2) == (64, 0)
    assert gmm_kernel.count_visits(np.eye(32, dtype=int)[5] * 2048, 1) == (1, 15)


@pytest.fixture
def roads(monkeypatch):
    """Which kernel ``moe._gmm`` sent each call to, and at what tiles."""
    taken = []
    own, theirs = gmm_kernel.gmm, megablox.gmm

    def new(lhs, rhs, sizes, **kw):
        taken.append(("gmm_kernel", kw["tk"], kw["tn"]))
        return own(lhs, rhs, sizes, **kw)

    def old(lhs, rhs, sizes, **kw):
        taken.append(("megablox", kw["tiling"]))
        return theirs(lhs, rhs, sizes, **kw)

    monkeypatch.setattr(gmm_kernel, "gmm", new)
    monkeypatch.setattr(megablox, "gmm", old)
    return taken


@pytest.mark.parametrize("k, n, road", [
    (128, 256, ("gmm_kernel", 128, 256)),
    (256, 128, ("gmm_kernel", 256, 128)),
    (64, 32, ("megablox", (128, 128, 128))),  # the tests' tiny presets
    (128, 96, ("megablox", (128, 128, 128))),
], ids=["whole_tiles", "whole_tiles_down", "tiny", "ragged_width"])
def test_the_road_is_chosen_by_shape(roads, k, n, road):
    lhs, rhs = operands(np.random.default_rng(1), 128, 2, k, n, jnp.float32)
    sizes = jnp.asarray([30, 50], jnp.int32)
    out = np.asarray(moe._gmm(lhs, rhs, sizes, interpret=True))
    assert roads == [road]
    want = np.concatenate([np.asarray(lhs)[:30] @ np.asarray(rhs)[0],
                           np.asarray(lhs)[30:80] @ np.asarray(rhs)[1]])
    np.testing.assert_allclose(out[:80], want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hidden, inter, tiles", [
    (2048, 1792, 2 * 1 + 1),  # lfm2-8b-a1b: a matrix of 7.3 MB a block
    (2048, 768, 2 * 1 + 1),  # sdar-30b-a3b-chat
    (2048, 512, 2 * 1 + 1),  # laguna-xs.2
    (2304, 1024, 2 * 1 + 1),  # kimi-linear
    (4096, 2048, 2 * 2 + 2),  # mimo-v2.5: 16 MiB a matrix, 8 MiB a block
    (4096, 14336, 2 * 14 + 0),  # mixtral-8x7b: down is 14336 deep (megablox)
    (64, 32, 0),
], ids=["lfm2", "sdar", "laguna", "kimi", "mimo", "mixtral", "tiny"])
def test_column_tiles_of_a_sparse_layer(hidden, inter, tiles):
    assert moe.gmm_column_tiles(hidden, inter) == tiles


@pytest.mark.parametrize("held, n_live", [
    (None, None), (None, 21), ((4, 4), 21), ((4, 4), None)],
    ids=["all", "live_prefix", "held_and_live", "held"])
def test_grouped_moe_is_bit_equal_on_either_kernel(monkeypatch, held, n_live):
    """``_grouped_moe`` on the repo's kernel against itself on megablox at
    the same tiles: a ``[L * E]`` stack of 3 x 4 experts of which one
    layer's are ``held``, pad tokens past ``n_live``."""
    rng = np.random.default_rng(7)
    t, d, f, e, k = 32, 128, 256, 12, 2
    hidden = jnp.asarray(rng.standard_normal((t, d)) * 0.3, jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.bfloat16)
    first, count = held or (0, e)
    ids = jnp.asarray(rng.integers(first - 1, first + count + 1, (t, k)) % e, jnp.int32)
    w = jnp.asarray(rng.random((t, k)), jnp.float32)
    live = None if n_live is None else jnp.arange(t) < n_live

    def run():
        return np.asarray(moe._grouped_moe(
            hidden, wg, wu, wd, w, ids, interpret=True, held=held,
            live=live).astype(jnp.float32))

    ours = run()
    monkeypatch.setattr(moe, "_gmm", lambda lhs, rhs, sizes, interpret: megablox.gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.float32,
        interpret=interpret, tiling=moe._gmm_tiling(*rhs.shape[1:])))
    theirs = run()
    np.testing.assert_array_equal(ours, theirs)
    assert np.abs(ours[:n_live]).max() > 1e-3
    if n_live is not None:
        assert not ours[n_live:].any()


def test_the_runner_counts_visits_where_the_kernel_runs(monkeypatch):
    """``ModelRunner._gmm_column_tiles``: the layer's column tiles on one
    TPU chip, 0 elsewhere and for a model without experts; the counts a
    step adds are ``count_visits`` of the live tokens an expert a layer."""
    import types

    from vllm_tpu.worker.model_runner import ModelRunner

    prop = ModelRunner._gmm_column_tiles.func
    lfm2 = types.SimpleNamespace(model=types.SimpleNamespace(
        hidden_size=2048, moe_intermediate=1792))
    dense = types.SimpleNamespace(model=types.SimpleNamespace(hidden_size=4096))
    assert prop(lfm2) == 0  # the CPU: the dense path is the path
    monkeypatch.setattr(moe, "_grouped_is_the_path", lambda: True)
    assert prop(lfm2) == 3 and prop(dense) == 0
    counts = np.full((14, 32), 64)
    counts[:, 0], counts[:, -1] = 32, 96
    assert gmm_kernel.count_visits(counts, 3) == (14 * 32 * 3, 14 * 15 * 3)
