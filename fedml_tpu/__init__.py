"""fedml_tpu — a TPU-native federated / distributed ML framework.

Re-designed from scratch for JAX/XLA/pjit (capability reference: FedML —
see SURVEY.md).  Top-level API mirrors the reference's
(``python/fedml/__init__.py``): ``init()``, ``run_simulation()``, plus the
typed ``Config`` replacing the duck-typed args namespace.
"""

from __future__ import annotations

import logging
from typing import Optional

__version__ = "0.1.0"

from . import constants  # noqa: E402
from .arguments import Config, add_args, load_arguments  # noqa: E402


def init(args: Optional[Config] = None, argv=None) -> Config:
    """Bootstrap: parse args/YAML, seed host RNGs, set up logging.

    Reference: ``fedml.init`` (``python/fedml/__init__.py:64``) — env-version
    resolution, seeding, per-platform arg mangling.  The TPU build needs no
    spawn-mode multiprocessing or MPI rank discovery for simulation (the mesh
    replaces worker processes); cross-silo rank/role come from the Config.
    """
    from .core import rng
    from .obs import trace as obstrace

    # THE listener behind fedml_xla_* (compiles, persistent-cache loads)
    obstrace.install_xla_listener()
    with obstrace.traced("entry.init"):
        cfg = args if args is not None else add_args(argv)
        rng.seed_everything(cfg.random_seed)
        logging.basicConfig(
            level=logging.INFO,
            format="[fedml_tpu] %(asctime)s %(levelname)s %(message)s",
        )
        # every entry point compiles through the persistent cache, placed by the
        # one rule in core/cache.py ($JAX_COMPILATION_CACHE_DIR, else a fixed
        # path in the checkout)
        from .core.cache import setup_persistent_cache

        setup_persistent_cache()
        # MULTIPROCESS/MPI backend: bring up jax.distributed before any backend
        # use so the mesh spans all hosts (reference: MPI rank discovery in
        # fedml.init; here the coordination service replaces mpi4py).
        from .parallel import multihost

        requested = getattr(cfg, "backend_sim", "") in (
            "MULTIPROCESS", constants.SIMULATION_BACKEND_MPI,
        )
        from .core.flags import cfg_extra

        if requested or cfg_extra(cfg, "coordinator_address"):
            up = multihost.ensure_initialized(cfg)
            if requested and not up:
                # an explicitly requested multi-process backend must never
                # silently degrade to single-process (the other hosts would block
                # forever in the coordination barrier)
                raise ValueError(
                    "backend_sim=MULTIPROCESS requires coordinator config: set "
                    "cfg.extra coordinator_address/num_processes/process_id or "
                    "the JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID "
                    "environment variables on every host"
                )
    return cfg


def run_simulation(cfg: Optional[Config] = None, backend: Optional[str] = None):
    """One-line simulation entry (reference ``launch_simulation.py:9``)."""
    from .runner import FedMLRunner

    cfg = init(cfg)
    if backend:
        cfg.backend_sim = backend
    runner = FedMLRunner(cfg)
    return runner.run()


def run_cross_silo_server(cfg: Optional[Config] = None):
    """Reference ``launch_cross_silo_horizontal.py:7``."""
    from .runner import FedMLRunner

    cfg = init(cfg)
    cfg.training_type = constants.TRAINING_PLATFORM_CROSS_SILO
    cfg.role = "server"
    return FedMLRunner(cfg).run()


def run_cross_silo_client(cfg: Optional[Config] = None):
    """Reference ``launch_cross_silo_horizontal.py:28``."""
    from .runner import FedMLRunner

    cfg = init(cfg)
    cfg.training_type = constants.TRAINING_PLATFORM_CROSS_SILO
    cfg.role = "client"
    return FedMLRunner(cfg).run()
