"""Production meshes + the ``jax.distributed`` multi-host on-ramp.

Functions, not module-level constants — importing this module never
touches jax device state (device count is locked at first jax init, and
smoke tests must see 1 device while the dry-run sees 512)."""
from __future__ import annotations

import os

import jax

# process-level latch: jax.distributed.initialize may run at most once
_distributed = {"initialized": False}


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types: jax's default is Explicit,
    and every mesh in this repo relies on sharding propagation."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one 256-chip v5e pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (subprocesses set
    --xla_force_host_platform_device_count accordingly)."""
    return make_mesh(shape, axes)


def make_sessions_mesh(n_shards=None, *, axis=None):
    """1-D fleet-serving mesh over the session axis.

    ``ShardedFleetBackend`` shards its (N, W, d) session rings over this
    axis; defaults to every visible device (1 on a plain test process,
    ``--xla_force_host_platform_device_count`` many in the forced-host
    multi-shard tests and benchmarks)."""
    from repro.distributed.sharding import SESSIONS_AXIS
    n = len(jax.devices()) if n_shards is None else n_shards
    return make_mesh((n,), (axis or SESSIONS_AXIS,))


def maybe_init_distributed(*, env=None, initialize=None) -> bool:
    """The multi-host on-ramp: initialize ``jax.distributed`` from the
    launcher environment, or no-op in a plain single-process run.

    Environment contract (presence of the coordinator turns this on)::

        REPRO_COORDINATOR    host:port of process 0's coordinator service
        REPRO_NUM_PROCESSES  total process count           (default 1)
        REPRO_PROCESS_ID     this process's index           (default 0)

    Call it before the first jax device query (first thing in a launcher
    ``main``): after ``jax.distributed.initialize``, ``jax.devices()``
    returns the GLOBAL device list, so ``make_sessions_mesh()`` with no
    argument spans the whole job and the sharded fleet/dispatch planes
    scale out with zero further configuration.  Returns True when the
    process joined (or had already joined) a distributed job, False for
    the single-process no-op.  Idempotent per process.

    ``env``/``initialize`` are injection seams for tests — real callers
    pass neither (``os.environ`` / ``jax.distributed.initialize``).
    """
    env = os.environ if env is None else env
    coordinator = env.get("REPRO_COORDINATOR")
    if not coordinator:
        return False
    if _distributed["initialized"]:
        return True
    n_proc = int(env.get("REPRO_NUM_PROCESSES", "1"))
    proc_id = int(env.get("REPRO_PROCESS_ID", "0"))
    if not 0 <= proc_id < n_proc:
        raise ValueError(
            f"REPRO_PROCESS_ID={proc_id} out of range for "
            f"REPRO_NUM_PROCESSES={n_proc}")
    init = jax.distributed.initialize if initialize is None else initialize
    init(coordinator_address=coordinator, num_processes=n_proc,
         process_id=proc_id)
    _distributed["initialized"] = True
    return True
