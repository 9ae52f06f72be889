"""Device-mesh construction for the parallelism suite.

Reference analog: ``vllm/distributed/parallel_state.py`` — where the
reference builds TP/PP/DP/EP/CP torch process groups with rank arithmetic
(:1494-1694), the TPU design is a single ``jax.sharding.Mesh`` whose named
axes ARE the parallel groups; XLA lowers collectives onto ICI/DCN.

Axis order is (dp, pp, cp, tp): tp innermost so tensor-parallel collectives
ride the fastest ICI links, matching the reference's rank layout
``ExternalDP x DP x PP x PCP x TP`` (parallel_state.py:1560).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from vllm_tpu.config import ParallelConfig
from vllm_tpu.logger import init_logger

logger = init_logger(__name__)

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_CP = "cp"
AXIS_TP = "tp"


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """``jax.shard_map`` with this repo's defaults: manual over
    ``axis_names`` only (None = every mesh axis), replication unchecked."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kwargs,
    )


def build_mesh(parallel_config: ParallelConfig, devices=None) -> Mesh:
    pc = parallel_config
    devices = devices if devices is not None else jax.devices()
    world = pc.world_size
    if len(devices) < world:
        raise ValueError(
            f"parallel config needs {world} devices, have {len(devices)}"
        )
    shape = (
        pc.data_parallel_size,
        pc.pipeline_parallel_size,
        pc.context_parallel_size,
        pc.tensor_parallel_size,
    )
    grid = np.asarray(devices[:world]).reshape(shape)
    mesh = Mesh(grid, (AXIS_DP, AXIS_PP, AXIS_CP, AXIS_TP))
    logger.info("device mesh: %s", dict(zip(mesh.axis_names, mesh.devices.shape)))
    return mesh


def named_shardings(mesh: Mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree.

    Descends registered dataclass nodes (e.g. QuantizedLinear), treating
    only PartitionSpec values as leaves.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
