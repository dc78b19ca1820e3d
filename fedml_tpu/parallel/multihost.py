"""Multi-host (multi-process) execution — the MULTIPROCESS backend.

Parity target: the reference's MPI simulation platform
(``simulation/mpi/fedavg/FedAvgAPI.py:13`` — 1 server + N worker ranks over
``mpi4py``) and its NCCL/gloo process groups.  TPU-native translation: the
SAME single-controller-looking program runs on every host
(multi-controller JAX); ``jax.distributed.initialize`` wires the
coordination service, the global ``Mesh`` spans all hosts' devices, and the
collectives that the MPI ranks did by hand (send/recv of model state) become
GSPMD all-reduces over ICI/DCN.  No actor hierarchy, no rank-0 parameter
server: every process executes the identical jitted round and holds the
identical replicated global state.

Run the same script on every host with either
- env: JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID
  (standard jax.distributed envs also work: COORDINATOR_ADDRESS, ...), or
- cfg.extra: coordinator_address / num_processes / process_id.

CPU-backed multi-process (gloo collectives) is first-class for CI: the
2-process test in ``tests/test_multihost.py`` asserts numerics equal the
single-process mesh run.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Optional

import jax
import numpy as np

log = logging.getLogger("fedml_tpu.parallel.multihost")


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def ensure_initialized(cfg=None) -> bool:
    """Initialize jax.distributed from config/env if requested and not yet up.

    Returns True when running multi-process after the call.  Safe to call
    multiple times and from single-process runs (no-ops).
    """
    if jax.distributed.is_initialized():
        # by an earlier call, or by the launcher / user script (standard
        # multi-host launchers call it before user code) — adopt it rather
        # than crash on a second initialize
        return jax.process_count() > 1
    from ..core.flags import cfg_extra

    coord = (
        cfg_extra(cfg, "coordinator_address")
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("COORDINATOR_ADDRESS")
    )
    if not coord:
        # single-process run; jax.process_count may initialize the backend,
        # which is fine at this point
        return jax.process_count() > 1
    nproc = int(cfg_extra(cfg, "num_processes") or os.environ.get("JAX_NUM_PROCESSES") or 0)
    pid = cfg_extra(cfg, "process_id", os.environ.get("JAX_PROCESS_ID"))
    kwargs: dict[str, Any] = {"coordinator_address": coord}
    if nproc:
        kwargs["num_processes"] = nproc
    if pid is not None:
        kwargs["process_id"] = int(pid)
    jax.distributed.initialize(**kwargs)
    log.info(
        "jax.distributed up: process %d/%d, %d global devices (%d local)",
        jax.process_index(), jax.process_count(), len(jax.devices()), len(jax.local_devices()),
    )
    return True


def make_global_array(x, sharding) -> jax.Array:
    """Build a globally-sharded array from a host-replicated numpy array.

    Every process holds the identical FULL array (fedml_tpu's data loading is
    deterministic per seed, so all hosts materialize the same shards — no
    host-to-host scatter needed); each contributes only its addressable
    shards, sliced out by index.  Single-process this is just device_put.
    """
    if not is_multiprocess():
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def fetch_replicated(tree):
    """device_get for multi-controller: replicated outputs are addressable
    on every host, so plain device_get works; this wrapper documents the
    invariant and asserts it in debug runs."""
    return jax.device_get(tree)


def sync_global_devices(tag: str = "fedml_tpu") -> None:
    if is_multiprocess():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)
