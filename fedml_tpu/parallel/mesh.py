"""Mesh construction for FL-on-TPU.

The reference scales by spawning processes (MPI ranks, torchrun DDP groups,
NCCL LocalAggregators — SURVEY.md §2.14 P1-P5).  Here the same strategies are
expressed as axes of one ``jax.sharding.Mesh``:

- simulation (P1-P3):  1-D ``("clients",)`` axis — each shard simulates a
  subset of clients; aggregation is a mean over the stacked-client dim that
  GSPMD lowers to an ICI all-reduce.
- intra-silo DP (P4):  ``("data",)`` axis — batch-sharded local SGD.
- hierarchical (P5):   2-D ``("silo", "data")`` — outer FL axis over DCN
  (multi-slice), inner DP axis over ICI.
- ZeRO-3 (P6):         parameter shardings over the ``data`` axis (GSPMD
  handles gather/scatter natively).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_CLIENTS = "clients"
AXIS_DATA = "data"
AXIS_SILO = "silo"
AXIS_MODEL = "model"  # tensor-parallel axis (beyond reference parity)
AXIS_SEQ = "seq"  # context/sequence-parallel axis (ring attention)


def make_mesh(
    axis_names: Sequence[str] = (AXIS_CLIENTS,),
    axis_sizes: Optional[Sequence[int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh over the available devices.

    If ``axis_sizes`` is None the first axis absorbs all devices.  Sizes may
    use -1 for "remaining devices" (like a reshape).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if axis_sizes is None:
        axis_sizes = [n] + [1] * (len(axis_names) - 1)
    sizes = list(axis_sizes)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {dict(zip(axis_names, sizes))} needs {total} devices, have {n}")
    if total < n and devices is None:
        # an explicit device list is a deliberate carve (submeshes, tests);
        # a shape that leaves part of the default fleet idle is worth a word
        import logging

        logging.getLogger("fedml_tpu.parallel.mesh").warning(
            "mesh %s uses %d of %d visible devices; the other %d stay idle",
            dict(zip(axis_names, sizes)), total, n, n - total)
    dev_array = np.array(devs[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(axis_names))


def parse_mesh_shape(spec: str) -> tuple[list[str], list[int]]:
    """Parse ``"clients:8"`` / ``"silo:2,data:4"`` from Config.mesh_shape."""
    names, sizes = [], []
    for part in spec.split(","):
        name, _, size = part.strip().partition(":")
        names.append(name)
        sizes.append(int(size) if size else -1)
    return names, sizes


def mesh_from_config(cfg, devices=None) -> Mesh:
    if getattr(cfg, "mesh_shape", ""):
        names, sizes = parse_mesh_shape(cfg.mesh_shape)
        return make_mesh(names, sizes, devices)
    return make_mesh((AXIS_CLIENTS,), None, devices)


class SubmeshPlan:
    """A partition of the fleet's device array into disjoint per-job Meshes.

    Each lease is a contiguous slice of the device list reshaped to the SAME
    axis names/sizes, so a job's NamedShardings, pjit server fold, and AOT
    fingerprints (mesh shape is a fingerprint component) all resolve against
    its lease exactly as they would against a dedicated fleet of that shape —
    which is what makes submesh-vs-dedicated bitwise parity possible.
    """

    def __init__(self, submeshes: Sequence[Mesh], axis_names: Sequence[str],
                 axis_sizes: Sequence[int]):
        if not submeshes:
            raise ValueError("SubmeshPlan needs at least one submesh")
        self.submeshes = list(submeshes)
        self.axis_names = tuple(axis_names)
        self.axis_sizes = tuple(int(s) for s in axis_sizes)

    def __len__(self) -> int:
        return len(self.submeshes)

    def lease(self, index: int) -> Mesh:
        """The submesh of lease slot ``index`` (jobs hold a slot index, not
        a Mesh — the scheduler maps grant -> lease through this)."""
        return self.submeshes[index % len(self.submeshes)]

    def describe(self) -> dict:
        return {
            "jobs": len(self.submeshes),
            "shape": dict(zip(self.axis_names, self.axis_sizes)),
            "devices_per_job": int(np.prod(self.axis_sizes)),
        }


def carve_submeshes(
    axis_names: Sequence[str],
    axis_sizes: Sequence[int],
    n_jobs: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> SubmeshPlan:
    """Carve ``n_jobs`` disjoint contiguous submeshes of shape
    ``axis_names x axis_sizes`` out of the device list.

    Raises ``ValueError`` when the shapes do not tile the fleet (per-job
    size not concrete, or n_jobs x per-job devices exceeds the fleet) —
    callers fall back to the time-sliced gate on that error.
    """
    devs = list(devices if devices is not None else jax.devices())
    sizes = [int(s) for s in axis_sizes]
    if any(s <= 0 for s in sizes):
        raise ValueError(
            f"submesh shape {dict(zip(axis_names, sizes))} must be concrete "
            "(no -1 / zero axes) to tile the fleet")
    per = int(np.prod(sizes))
    n_jobs = int(n_jobs)
    if n_jobs < 1:
        raise ValueError(f"n_jobs must be >= 1, got {n_jobs}")
    if per * n_jobs > len(devs):
        raise ValueError(
            f"{n_jobs} submeshes of {per} devices need {per * n_jobs}, "
            f"fleet has {len(devs)}")
    subs = []
    for i in range(n_jobs):
        chunk = devs[i * per:(i + 1) * per]
        subs.append(Mesh(np.array(chunk).reshape(sizes), tuple(axis_names)))
    return SubmeshPlan(subs, axis_names, sizes)


def submesh_plan_from_config(cfg, devices=None) -> Optional[SubmeshPlan]:
    """Build the fleet partition from ``extra.mt_submesh_shape`` /
    ``mt_submesh_jobs``, or None (LOUDLY) when unset or the shapes do not
    tile the fleet — None means the control plane keeps the PR-14
    time-sliced gate, bit-identical."""
    import logging

    from ..core.flags import cfg_extra

    spec = cfg_extra(cfg, "mt_submesh_shape")
    if not spec:
        return None
    names, sizes = parse_mesh_shape(spec)
    devs = list(devices if devices is not None else jax.devices())
    n_jobs = cfg_extra(cfg, "mt_submesh_jobs")
    try:
        if n_jobs is None:
            per = int(np.prod([s for s in sizes if s > 0]))
            if any(s <= 0 for s in sizes) or per <= 0:
                raise ValueError(
                    f"submesh shape {spec!r} must be concrete to derive "
                    "mt_submesh_jobs")
            n_jobs = len(devs) // per
        return carve_submeshes(names, sizes, n_jobs, devs)
    except ValueError as e:
        logging.getLogger("fedml_tpu.parallel.mesh").warning(
            "mt_submesh_shape=%r rejected (%s); falling back to the "
            "time-sliced round gate", spec, e)
        return None


def round_up(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` >= ``n`` (client-axis padding math)."""
    return -(-n // multiple) * multiple


def pad_leading_axis_np(tree, n_target: int):
    """Zero-pad every leaf's leading axis to ``n_target`` rows (host-side).

    The one place client-axis pad-row semantics live: pad rows are ZEROS
    (zero-count dummies are never sampled, gathered for real lanes, or
    scattered to — engine invariants), used both at stack build and at
    checkpoint restore."""
    import numpy as np

    def pad(a):
        a = np.asarray(a)
        if n_target <= a.shape[0]:
            return a
        extra = np.zeros((n_target - a.shape[0],) + a.shape[1:], a.dtype)
        return np.concatenate([a, extra])

    return jax.tree_util.tree_map(pad, tree)


def client_sharding(mesh: Mesh, axis: str = AXIS_CLIENTS) -> NamedSharding:
    """Sharding for arrays with a leading stacked-clients dimension."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_leading_axis(tree, mesh: Mesh, axis: str = AXIS_CLIENTS, warn: bool = True):
    """Place a stacked pytree with its leading dim sharded over ``axis``.

    Leading dims not divisible by the axis size are replicated instead —
    correctness over parallelism for small client counts — but LOUDLY: a
    127-client stack on an 8-device axis silently losing all client
    parallelism is a perf cliff, so each distinct undivisible leading dim
    warns once per process.

    Multi-process aware: when the mesh spans hosts, arrays are assembled via
    make_array_from_callback (each host contributes its addressable shards).
    """
    import warnings

    from .multihost import make_global_array

    if axis not in mesh.shape:
        if axis != AXIS_CLIENTS:
            # an explicit axis that doesn't exist is a caller bug, not a
            # convention to paper over
            raise KeyError(
                f"mesh has no axis {axis!r} (axes: {mesh.axis_names}); "
                "pass one of the mesh's axes"
            )
        # the default stacked-clients axis on a mesh without one (e.g.
        # hierarchical's 2-D ("silo", "data")) shards over the FIRST axis —
        # the outer FL axis by this module's convention (P5 row above) —
        # and says so
        import warnings

        warnings.warn(
            f"shard_leading_axis: mesh has no {AXIS_CLIENTS!r} axis; "
            f"sharding the stacked-client dim over {mesh.axis_names[0]!r} "
            f"(the outer axis of {dict(mesh.shape)})",
            stacklevel=3,
        )
        axis = mesh.axis_names[0]
    size = mesh.shape[axis]

    def put(x):
        if x.ndim >= 1 and x.shape[0] % size == 0:
            spec = P(axis, *([None] * (x.ndim - 1)))
        else:
            if warn and x.ndim >= 1 and x.shape[0] > 1 and size > 1:
                key = (int(x.shape[0]), int(size))
                if key not in _undivisible_warned:
                    _undivisible_warned.add(key)
                    warnings.warn(
                        f"shard_leading_axis: leading dim {x.shape[0]} is not "
                        f"divisible by mesh axis {axis!r} size {size}; "
                        "REPLICATING instead — all parallelism over this axis "
                        "is lost for these arrays. Pad the client stack to a "
                        f"multiple of {size} (e.g. round client_num_per_round "
                        "up) to regain it.",
                        stacklevel=3,
                    )
            spec = P()
        return make_global_array(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


_undivisible_warned: set = set()


def replicate(tree, mesh: Mesh):
    from .multihost import make_global_array

    rep = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: make_global_array(x, rep), tree)
