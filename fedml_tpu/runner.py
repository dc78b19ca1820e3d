"""FedMLRunner — platform dispatch.

Reference: ``python/fedml/runner.py:19`` picks a platform runner from
``args.training_type``/``args.backend``.  Same dispatch here; the simulation
path constructs the MeshSimulator directly (no actor hierarchy to build).
"""

from __future__ import annotations

from typing import Optional

from . import constants as C
from .core.flags import cfg_extra
from .arguments import Config


def _check_unimplemented_flags(cfg: Config) -> None:
    """Security/privacy flags must never be silent no-ops: until the trust
    stack handles a flag, enabling it is an error (silent absence of DP noise
    or defenses is worse than a crash)."""
    pending = [
        name
        for name in ("enable_attack", "enable_defense", "enable_dp", "enable_secagg", "enable_fhe", "enable_contribution")
        if getattr(cfg, name, False) and name not in _IMPLEMENTED_TRUST_FLAGS
    ]
    if pending:
        raise NotImplementedError(
            f"trust features {pending} are enabled in the config but not yet "
            "implemented in fedml_tpu; refusing to run without them"
        )


# updated as the trust stack lands
_IMPLEMENTED_TRUST_FLAGS: set = {
    "enable_attack",
    "enable_defense",
    "enable_dp",
    "enable_contribution",
    "enable_secagg",  # LightSecAgg masked aggregation (cross-silo platform)
    "enable_fhe",  # RLWE homomorphic aggregation (cross-silo platform)
}


class FedMLRunner:
    def __init__(
        self,
        cfg: Config,
        dataset=None,
        model=None,
        client_trainer=None,
        server_aggregator=None,
    ):
        self.cfg = cfg
        self.dataset = dataset
        self.model = model
        self.client_trainer = client_trainer
        self.server_aggregator = server_aggregator
        _check_unimplemented_flags(cfg)
        from .obs.trace import traced  # imports jax, which importing this module does not

        with traced("entry.runner", training_type=cfg.training_type):
            if cfg.training_type == C.TRAINING_PLATFORM_SIMULATION:
                self.runner = self._init_simulation_runner()
            elif cfg.training_type == C.TRAINING_PLATFORM_CROSS_SILO:
                self.runner = self._init_cross_silo_runner()
            elif cfg.training_type == C.TRAINING_PLATFORM_CROSS_DEVICE:
                self.runner = self._init_cross_device_runner()
            elif cfg.training_type == C.TRAINING_PLATFORM_CROSS_CLOUD:
                self.runner = self._init_cross_cloud_runner()
            elif cfg.training_type == C.TRAINING_PLATFORM_SERVING:
                self.runner = self._init_serving_runner()
            elif cfg.training_type == C.TRAINING_PLATFORM_CENTRALIZED:
                self.runner = self._init_centralized_runner()
            else:
                raise ValueError(f"unsupported training_type {cfg.training_type!r}")

    def _load_data_model(self):
        if self.dataset is None:
            from .data import loader

            self.dataset = loader.load(self.cfg)
        if self.model is None:
            from .models import model_hub

            self.model = model_hub.create(self.cfg, self.dataset.class_num)
        return self.dataset, self.model

    # simulators that bypass the MeshSimulator (and its trust pipeline /
    # custom-trainer support)
    _SPECIAL_SIM_OPTIMIZERS = {
        C.FEDERATED_OPTIMIZER_DECENTRALIZED_FL,
        C.FEDERATED_OPTIMIZER_HIERARCHICAL_FL,
        C.FEDERATED_OPTIMIZER_ASYNC_FEDAVG,
        C.FEDERATED_OPTIMIZER_SPLIT_NN,
        C.FEDERATED_OPTIMIZER_FEDGKT,
        C.FEDERATED_OPTIMIZER_VERTICAL_FL,
        C.FEDERATED_OPTIMIZER_FEDGAN,
        C.FEDERATED_OPTIMIZER_FEDNAS,
        C.FEDERATED_OPTIMIZER_FEDSEG,
        C.FEDERATED_OPTIMIZER_TURBO_AGGREGATE,
        C.FEDERATED_OPTIMIZER_FEDLLM,
        *C.FEDERATED_OPTIMIZER_MYAVG_ALIASES,
    }
    # these build their own model pair internally; model_hub model is unused
    _OWN_MODEL_OPTIMIZERS = {
        C.FEDERATED_OPTIMIZER_SPLIT_NN,
        C.FEDERATED_OPTIMIZER_FEDGKT,
        C.FEDERATED_OPTIMIZER_VERTICAL_FL,
        C.FEDERATED_OPTIMIZER_FEDGAN,
        C.FEDERATED_OPTIMIZER_FEDNAS,
        C.FEDERATED_OPTIMIZER_FEDSEG,
        C.FEDERATED_OPTIMIZER_FEDLLM,
    }

    def _init_simulation_runner(self):
        for flag, feature in (("enable_secagg", "LightSecAgg"), ("enable_fhe", "FHE aggregation")):
            if getattr(self.cfg, flag, False):
                raise NotImplementedError(
                    f"{flag} is a cross-silo protocol feature ({feature} over "
                    "the wire); the single-process simulator has no "
                    "adversarial server to hide updates from — set "
                    "training_type='cross_silo'"
                )
        opt = self.cfg.federated_optimizer
        if opt in self._SPECIAL_SIM_OPTIMIZERS:
            # trust flags must never be silent no-ops (see
            # _check_unimplemented_flags): these simulators don't wire the
            # trust pipeline yet, so refuse rather than ignore.  MyAvg is the
            # exception — it routes attack/defense/DP through the engine's
            # trust hooks and enforces its own finer-grained policy
            # (sim/myavg.py refuses secagg/fhe/contribution and
            # aggregation-replacing defenses itself).
            if opt not in C.FEDERATED_OPTIMIZER_MYAVG_ALIASES:
                active = [
                    f for f in _IMPLEMENTED_TRUST_FLAGS if getattr(self.cfg, f, False)
                ]
                if active:
                    raise NotImplementedError(
                        f"trust features {active} are not yet wired into the "
                        f"{opt!r} simulator (supported on the FedAvg-family mesh "
                        "engine); refusing to run without them"
                    )
            if self.client_trainer is not None or self.server_aggregator is not None:
                raise ValueError(
                    f"custom client_trainer/server_aggregator are not used by "
                    f"the {opt!r} simulator; remove them or use a FedAvg-family optimizer"
                )
        if self.dataset is None:
            from .data import loader

            self.dataset = loader.load(self.cfg)
        dataset = self.dataset
        if self.model is None and opt not in self._OWN_MODEL_OPTIMIZERS:
            from .models import model_hub

            self.model = model_hub.create(self.cfg, dataset.class_num)
        model = self.model
        if opt == C.FEDERATED_OPTIMIZER_DECENTRALIZED_FL:
            from .sim.decentralized import DecentralizedSimulator

            return DecentralizedSimulator(self.cfg, dataset, model)
        if opt == C.FEDERATED_OPTIMIZER_HIERARCHICAL_FL:
            from .sim.hierarchical import HierarchicalSimulator

            return HierarchicalSimulator(self.cfg, dataset, model)
        if opt == C.FEDERATED_OPTIMIZER_ASYNC_FEDAVG:
            from .sim.async_fl import AsyncSimulator

            return AsyncSimulator(self.cfg, dataset, model)
        if opt == C.FEDERATED_OPTIMIZER_SPLIT_NN:
            from .sim.split_learning import SplitNNSimulator

            return SplitNNSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_FEDGKT:
            from .sim.split_learning import FedGKTSimulator

            return FedGKTSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_VERTICAL_FL:
            from .sim.vertical import VFLSimulator

            return VFLSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_FEDGAN:
            from .sim.fedgan import FedGANSimulator

            return FedGANSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_FEDNAS:
            from .sim.fednas import FedNASSimulator

            return FedNASSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_FEDSEG:
            from .sim.fedseg import FedSegSimulator

            return FedSegSimulator(self.cfg, dataset)
        if opt == C.FEDERATED_OPTIMIZER_TURBO_AGGREGATE:
            from .sim.turboaggregate import TurboAggregateSimulator

            return TurboAggregateSimulator(self.cfg, dataset, model)
        if opt in C.FEDERATED_OPTIMIZER_MYAVG_ALIASES:
            from .sim.myavg import MyAvgSimulator

            return MyAvgSimulator(self.cfg, dataset, model)
        if opt == C.FEDERATED_OPTIMIZER_FEDLLM:
            # config-driven FedLLM (reference spotlight_prj/fedllm
            # run_fedllm.py is launched from a job yaml); the transformer is
            # built internally from extra.llm_* keys / tiny defaults
            from .llm.fedllm import FedLLMSimulator

            return FedLLMSimulator(self.cfg, dataset)
        from .sim.engine import MeshSimulator

        return MeshSimulator(self.cfg, dataset, model, algorithm=self.client_trainer)

    def _init_cross_silo_runner(self):
        dataset, model = self._load_data_model()
        try:
            from .cross_silo import create_cross_silo_runner
        except ImportError as e:
            raise NotImplementedError(
                "cross_silo platform is not yet available in this build"
            ) from e
        return create_cross_silo_runner(self.cfg, dataset, model)

    def _init_cross_device_runner(self):
        dataset, model = self._load_data_model()
        from .cross_device import create_cross_device_runner

        return create_cross_device_runner(self.cfg, dataset, model)

    def _init_cross_cloud_runner(self):
        cfg = self.cfg
        llm_mode = bool(cfg_extra(cfg, "unitedllm"))
        if self.dataset is None:
            from .data import loader

            self.dataset = loader.load(cfg)
        if self.model is None and not llm_mode:
            from .models import model_hub

            self.model = model_hub.create(cfg, self.dataset.class_num)
        from .cross_cloud import create_cross_cloud_runner

        return create_cross_cloud_runner(cfg, self.dataset, self.model)

    def _init_serving_runner(self):
        """``training_type='model_serving'`` (reference ``runner.py:19`` +
        ``serving/fedml_server.py``): a federated run under an endpoint
        identity; the server registers + deploys the final model."""
        cfg = self.cfg
        for flag in ("enable_secagg", "enable_fhe"):
            if getattr(cfg, flag, False):
                # the serving managers wrap the PLAIN cross-silo builders;
                # silently dropping a privacy flag is worse than refusing
                raise NotImplementedError(
                    f"{flag} is not wired into the model_serving platform; "
                    "run the secure-aggregation job under "
                    "training_type='cross_silo' and deploy the result"
                )
        dataset, model = self._load_data_model()
        end_point = str(cfg_extra(cfg, "end_point_name", f"ep-{cfg.run_id}"))
        model_name = str(cfg_extra(cfg, "serving_model_name", cfg.model))
        version = str(cfg_extra(cfg, "model_version"))
        from .serving.federated import FedMLModelServingClient, FedMLModelServingServer

        if cfg.role == "server":
            single_process = cfg.backend in ("INPROC", "MESH", "")

            class _ServingRunner:
                def run(self_inner):
                    clients = []
                    if single_process:
                        from .comm.inproc import InProcRouter

                        InProcRouter.reset(str(getattr(cfg, "run_id", "0")))
                        clients = [
                            FedMLModelServingClient(
                                cfg, end_point, model_name, version,
                                dataset=dataset, model=model, rank=r,
                                backend="INPROC",
                            )
                            for r in range(1, cfg.client_num_in_total + 1)
                        ]
                        for c in clients:
                            c.run_in_thread()
                    server = FedMLModelServingServer(
                        cfg, end_point, model_name, version, dataset=dataset, model=model,
                        backend="INPROC" if single_process else None,
                    )
                    try:
                        history, _card = server.run()
                    finally:
                        for c in clients:
                            c.finish()
                    return history

            return _ServingRunner()

        class _ServingClientRunner:
            def run(self_inner):
                client = FedMLModelServingClient(
                    cfg, end_point, model_name, version, dataset=dataset, model=model,
                    rank=int(cfg.rank),
                )
                thread = client.run_in_thread()
                client.client.done.wait()
                thread.join(timeout=5.0)
                return None

        return _ServingClientRunner()

    def _init_centralized_runner(self):
        dataset, model = self._load_data_model()
        from .sim.centralized import CentralizedTrainer

        return CentralizedTrainer(self.cfg, dataset, model)

    def run(self):
        return self.runner.run()
