"""Multi-host runtime: jax.distributed bootstrap + global mesh.

Reference analog: ``vllm/distributed/parallel_state.py:1358``
(init_distributed_environment over torch ProcessGroups + NCCL) and the
``ExecutorWithExternalLauncher`` SPMD mode (``v1/executor/abstract.py``):
every host runs the same engine binary under an external launcher; the
TPU realization is ``jax.distributed.initialize`` — after it, every
process sees the GLOBAL device set, ``build_mesh`` lays axes over all
hosts, and GSPMD lowers cross-host collectives onto ICI/DCN exactly as it
does single-host onto ICI.

On real TPU pods ``jax.distributed.initialize()`` needs no arguments (the
TPU metadata service provides coordinator/topology); elsewhere — and in
the two-process CPU tests — the coordinator comes from env:

    VLLM_TPU_DIST_COORDINATOR  host:port of process 0
    VLLM_TPU_DIST_NUM_PROCESSES
    VLLM_TPU_DIST_PROCESS_ID

Unlike the original one-shot bootstrap, this module is RE-ENTRANT:
``shutdown_distributed()`` tears the runtime down (mesh-shrink recovery
re-bootstraps over the surviving hosts at a smaller world size), and
``init_distributed`` accepts explicit coordinator/num_processes/process_id
overrides so the recovery orchestrator does not have to mutate the
environment of a live process to re-mesh it.
"""

from __future__ import annotations

import gc
import os

import jax

from vllm_tpu.logger import init_logger
from vllm_tpu.resilience.failpoints import fail_point

logger = init_logger(__name__)

# Bootstrap state: "uninit" (never bootstrapped, or torn down),
# "multiproc" (jax.distributed runtime live), "uniproc" (single-process
# fallback — nothing to tear down). A plain bool could not distinguish
# "already up" from "deliberately torn down for re-bootstrap".
_state = "uninit"
_world: tuple[str, int, int] | None = None  # (coordinator, nproc, pid)


def _jax_client_live() -> bool:
    from jax._src import distributed as _dist

    return _dist.global_state.client is not None


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Bootstrap the multi-process JAX runtime (idempotent while up).

    Must run before anything initializes the XLA backend — so the check
    for an existing runtime reads jax's distributed global state rather
    than calling jax.process_count() (which would initialize it).

    Explicit arguments override the ``VLLM_TPU_DIST_*`` environment; the
    mesh-recovery path uses them to re-bootstrap the surviving hosts at a
    smaller world size after :func:`shutdown_distributed`.
    """
    global _state, _world
    if _state != "uninit":
        return
    if _jax_client_live():
        # A live client we did not create (external launcher already
        # bootstrapped this process). Adopt it.
        _state = "multiproc"
        return
    coordinator = (coordinator_address
                   or os.environ.get("VLLM_TPU_DIST_COORDINATOR"))
    if coordinator:
        # Explicit multi-process launch: failures here are user errors
        # and must propagate.
        nproc = (num_processes if num_processes is not None
                 else int(os.environ["VLLM_TPU_DIST_NUM_PROCESSES"]))
        pid = (process_id if process_id is not None
               else int(os.environ["VLLM_TPU_DIST_PROCESS_ID"]))
        _bootstrap_explicit(coordinator, nproc, pid)
        _world = (coordinator, nproc, pid)
    else:
        # TPU pods auto-discover via metadata; anywhere else (or when the
        # backend already initialized, e.g. a single-process launch of
        # the external backend) degrade to uniproc semantics.
        try:
            jax.distributed.initialize()
        except Exception as exc:
            logger.info("single-process fallback (%s)", exc)
            _state = "uniproc"
            return
        _world = None
    _state = "multiproc"
    logger.info(
        "distributed runtime: process %d/%d, %d global / %d local devices",
        jax.process_index(), jax.process_count(),
        len(jax.devices()), len(jax.local_devices()),
    )


def _bootstrap_explicit(coordinator: str, nproc: int, pid: int) -> None:
    """Bring up the jax.distributed runtime with a client that SURVIVES
    peer death.

    ``jax.distributed.initialize`` builds its client with the default
    missed-heartbeat callback — ``LOG(FATAL)`` — so a dead host takes
    every survivor down with it, which is precisely the failure mode the
    mesh-recovery subsystem exists to contain. Build the service/client
    by hand instead: a benign heartbeat callback (the mesh monitor owns
    death classification, on a much tighter timeout than the 100s
    coordination-service default), ``shutdown_on_destruction=False`` so
    dropping the handle in a forced teardown cannot re-enter the fatal
    path, and a short shutdown-barrier timeout so a graceful teardown
    racing a peer death fails fast instead of wedging recovery.
    """
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    state = _dist.global_state
    if pid == 0 and state.service is None:
        bind = "[::]:" + coordinator.rsplit(":", 1)[1]
        state.service = _jax.get_distributed_runtime_service(bind, nproc)

    # Cross-process collectives on the CPU backend default to "none" —
    # any multi-host computation on the 2-process CPU rig then fails at
    # dispatch. Gloo ships with jaxlib; enable it before the backend is
    # created. TPU backends ignore this flag entirely.
    if "cpu" in (jax.config.jax_platforms or ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    def _on_missed_heartbeat(status) -> None:
        logger.error(
            "jax coordination-service heartbeat failure (a peer host is "
            "likely dead; mesh recovery will re-form the world): %s",
            status)

    client = _jax.get_distributed_runtime_client(
        coordinator, pid, init_timeout=120, shutdown_timeout=10,
        missed_heartbeat_callback=_on_missed_heartbeat,
        shutdown_on_destruction=False, use_compression=True)
    logger.info("connecting to jax distributed service at %s as process "
                "%d/%d", coordinator, pid, nproc)
    client.connect()
    state.coordinator_address = coordinator
    state.process_id = pid
    state.num_processes = nproc
    state.client = client


def _drop_service_and_reset() -> None:
    """Stop the coordination service (if this process hosts it) and reset
    jax's distributed global state. MUST run only after the old client is
    genuinely destroyed (see :func:`shutdown_distributed` for ordering):
    shutting the service down while any client still polls it delivers an
    error status into that client's heartbeat callback — and marshalling
    the status into Python from the C++ polling thread aborts the
    process."""
    from jax._src import distributed as _dist

    state = _dist.global_state
    if state.service is not None:
        try:
            state.service.shutdown()
        except Exception as exc:
            logger.warning("coordination service shutdown failed: %s", exc)
        state.service = None
    state.preemption_sync_manager = None
    state.process_id = 0
    state.num_processes = 1
    state.coordinator_address = None


def _clear_device_keyed_caches() -> None:
    """Purge every jax-internal ``functools.lru_cache`` whose keys can
    hold Device objects (e.g. ``pxla._create_da_object``). clear_caches/
    clear_backends miss these, and ONE cached Device reference keeps the
    whole old XLA client — and through its collectives, the old
    coordination client with its error-polling thread — alive. An undead
    coordination client is fatal on the next delivered error (the status
    cannot be marshalled into the Python heartbeat callback), so the
    sweep is belt-and-braces wide: every populated lru_cache in a jax
    module, not a hand-kept list that goes stale across jax upgrades.
    Teardown is a rare, already-expensive path; the scan cost is noise."""
    import functools

    # Mesh.__new__ interns every Mesh in a module-level dict keyed on
    # its device tuple; deleting the Mesh object does not evict it.
    from jax._src import mesh as _jmesh

    _jmesh._mesh_object_dict.clear()
    for obj in gc.get_objects():
        if type(obj) is not functools._lru_cache_wrapper:
            continue
        if not getattr(obj, "__module__", "").startswith("jax"):
            continue
        try:
            if obj.cache_info().currsize:
                obj.cache_clear()
        except Exception:
            continue


def shutdown_distributed(force: bool = False) -> None:
    """Tear down the jax.distributed runtime so a fresh
    :func:`init_distributed` can bootstrap a DIFFERENT world (the
    mesh-shrink path: survivors re-form at a smaller world size).

    ``force=True`` skips the cooperative shutdown barrier — mandatory
    when a peer is already dead: the dead host can never join the
    barrier, so the graceful path would stall for the barrier timeout
    and then fail anyway. Recovery tears down unilaterally; the mesh
    monitor already established who is alive.

    Also clears jax's cached XLA backends: the old backend holds device
    handles spanning the dead world, and any global arrays built on it
    are invalid after this call — callers must reload or re-replicate
    device state after the re-bootstrap.
    """
    global _state, _world
    if _state == "uninit":
        return
    graceful = False
    if _state == "multiproc" and _jax_client_live():
        if not force:
            try:
                jax.distributed.shutdown()
                graceful = True
            except Exception as exc:  # a dead peer can fail the barrier
                logger.warning("jax.distributed.shutdown failed: %s", exc)
        if not graceful:
            # Unilateral path. Ordering is LOAD-BEARING: the backend's
            # collectives hold a C++ reference to the coordination
            # client, so the client's error-polling thread stays alive
            # until the backend itself is destroyed. An error delivered
            # to that thread (the old service shutting down, or a NEW
            # service on the same port seeing the stale connection)
            # aborts the process while marshalling the status into the
            # Python heartbeat callback. So: drop the Python handle
            # first, destroy the backends, collect, and only THEN stop
            # the service / let a new world form.
            from jax._src import distributed as _dist

            _dist.global_state.client = None
    # Drop cached backends so the next backend init re-reads the (new)
    # distributed state instead of reusing devices of the dead world.
    # clear_backends() resets xla_bridge._backends, which is also the
    # sentinel jax.distributed.initialize() checks before allowing a
    # re-bootstrap — without it the smaller world can never form.
    jax.clear_caches()
    # _backends must be emptied IN PLACE before clear_backends rebinds
    # it: anything still holding the old dict object would, with it
    # populated, pin the old client forever.
    from jax._src import api as _jax_api
    from jax._src import xla_bridge as _xb

    _xb._backends.clear()
    # Not in the public namespace, but the only complete backend reset
    # (clears xla_bridge._backends and every pjit/dispatch cache pinned
    # to the old clients).
    _jax_api.clear_backends()
    _clear_device_keyed_caches()
    # Collect NOW so the old coordination client actually dies before a
    # new world forms on the same coordinator port: cycle-held backend
    # objects would otherwise keep its error-polling thread running
    # against the new service, which is fatal. Callers must have dropped
    # their own old-world Device/Array references (see
    # Worker.reinitialize_mesh).
    gc.collect()
    if not graceful:
        _drop_service_and_reset()
    _state = "uninit"
    _world = None


def is_distributed_initialized() -> bool:
    return _state != "uninit"


def distributed_world() -> tuple[str, int, int] | None:
    """(coordinator, num_processes, process_id) of the explicit world we
    bootstrapped, or None (uniproc / metadata-discovered)."""
    return _world


_barrier_seq = 0


def dist_barrier(tag: str = "", timeout_s: float = 60.0) -> None:
    """Cross-host synchronization point with a ``dist.barrier`` failpoint
    in front of it: ``delay``/``hang`` model a transient partition or a
    wedged peer holding up the collective (the mesh monitor — not this
    call — is responsible for deciding the peer is dead).

    Rides the coordination-service gRPC side channel rather than an XLA
    collective, so it works on backends without multiprocess collectives
    (the CPU test rig) and keeps working while the device fabric is the
    thing being debugged. Every process must call it the same number of
    times in the same order (the SPMD contract this repo already holds).
    """
    global _barrier_seq
    fail_point("dist.barrier", lambda: f"tag={tag}")
    if _state == "multiproc" and _jax_client_live():
        from jax._src import distributed as _dist

        _barrier_seq += 1
        key = f"vllm_tpu:{tag or 'barrier'}:{_barrier_seq}"
        try:
            _dist.global_state.client.wait_at_barrier(
                key, timeout_in_ms=int(timeout_s * 1000))
        except Exception as exc:
            logger.warning("dist_barrier(%s) failed: %s", tag, exc)
            raise


def replicate_to_global(tree, mesh):
    """Host data -> arrays replicated over the GLOBAL (multi-host) mesh.

    Every process must call this with IDENTICAL values (the SPMD external-
    launcher contract: same request stream, same scheduling decisions)."""
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        ) if hasattr(x, "shape") else x,
        tree,
    )
