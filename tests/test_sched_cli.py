"""Scheduler vertical + CLI tests: package -> agent -> subprocess -> status DB
-> logs, mirroring the reference launch pipeline (SURVEY.md §3.4) on the
local spool transport."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest


def _make_workspace(tmp_path: Path, body: str, job: str = "python main.py") -> Path:
    ws = tmp_path / "workspace"
    ws.mkdir()
    (ws / "main.py").write_text(body)
    job_yaml = tmp_path / "job.yaml"
    job_yaml.write_text(
        f"workspace: workspace\njob: \"{job}\"\n"
        "bootstrap: \"echo bootstrap-ran\"\n"
        "job_name: test_job\n"
        "computing:\n  minimum_num_gpus: 1\n"
    )
    return job_yaml


def test_launch_agent_pipeline(tmp_path):
    from fedml_tpu.sched.agent import FedMLAgent
    from fedml_tpu.sched.launch import FedMLLaunchManager

    spool = tmp_path / "spool"
    job_yaml = _make_workspace(tmp_path, "print('hello-from-job')\n")
    mgr = FedMLLaunchManager(str(spool))
    run_id = mgr.launch_job(str(job_yaml))
    assert run_id in mgr.list_queue()

    agent = FedMLAgent(str(spool))
    row = agent.wait_for(run_id, timeout=60)
    assert row["status"] == "FINISHED", row
    logs = agent.logs(run_id)
    assert "bootstrap-ran" in logs
    assert "hello-from-job" in logs


def test_agent_marks_failed_job(tmp_path):
    from fedml_tpu.sched.agent import FedMLAgent
    from fedml_tpu.sched.launch import FedMLLaunchManager

    spool = tmp_path / "spool"
    job_yaml = _make_workspace(tmp_path, "import sys; sys.exit(3)\n")
    run_id = FedMLLaunchManager(str(spool)).launch_job(str(job_yaml))
    agent = FedMLAgent(str(spool))
    row = agent.wait_for(run_id, timeout=60)
    assert row["status"] == "FAILED"
    assert row["returncode"] == 3


def test_resource_matcher():
    from fedml_tpu.sched.agent import match_resources

    jobs = [
        {"run_id": "big", "computing": {"minimum_num_gpus": 4}},
        {"run_id": "small", "computing": {"minimum_num_gpus": 1}},
    ]
    agents = [{"id": "a8", "num_devices": 8}, {"id": "a1", "num_devices": 1}]
    asg = match_resources(jobs, agents)
    assert asg["big"] == "a8"
    assert asg["small"] in ("a8", "a1")


def test_resource_matcher_type_and_memory():
    """Matcher honors device type and memory (reference scheduler_matcher):
    unmatchable jobs stay out of the assignment."""
    from fedml_tpu.sched.agent import match_resources

    jobs = [
        {"run_id": "tpu-job", "computing": {"minimum_num_gpus": 2, "request_gpu_type": "tpu-v5e"}},
        {"run_id": "mem-hog", "computing": {"minimum_num_gpus": 1, "minimum_memory_gb": 64}},
        {"run_id": "impossible", "computing": {"minimum_num_gpus": 99}},
    ]
    agents = [
        {"id": "cpu-box", "num_devices": 8, "device_type": "cpu", "mem_gb": 16},
        {"id": "tpu-box", "num_devices": 4, "device_type": "tpu-v5e", "mem_gb": 128},
    ]
    asg = match_resources(jobs, agents)
    assert asg["tpu-job"] == "tpu-box"          # type must match exactly
    assert asg["mem-hog"] == "tpu-box"          # only box with 64+ GB
    assert "impossible" not in asg              # nobody has 99 devices
    # free_devices (not raw capacity) is what the matcher consumes
    asg2 = match_resources(
        [{"run_id": "j", "computing": {"minimum_num_gpus": 4}}],
        [{"id": "busy", "num_devices": 8, "free_devices": 2}],
    )
    assert asg2 == {}


def test_agent_claims_only_fitting_jobs(tmp_path):
    """An agent must leave a too-big job in the queue for a bigger agent
    (round-3 verdict item 5a: 'any agent takes any job' is the gap)."""
    import yaml

    from fedml_tpu.sched.agent import FedMLAgent, registered_agents
    from fedml_tpu.sched.launch import FedMLLaunchManager

    spool = tmp_path / "spool"
    ws = tmp_path / "ws"
    ws.mkdir()
    (ws / "main.py").write_text("print('ok')\n")
    import sys

    job = {
        "workspace": "ws", "job": f"{sys.executable} main.py",
        "computing": {"minimum_num_gpus": 4, "request_gpu_type": "tpu-v5e"},
    }
    ypath = tmp_path / "job.yaml"
    ypath.write_text(yaml.safe_dump(job))
    mgr = FedMLLaunchManager(str(spool))
    run_id = mgr.launch_job(str(ypath))

    small = FedMLAgent(str(spool), agent_id="small",
                       capacity={"num_devices": 1, "device_type": "tpu-v5e"})
    wrong_type = FedMLAgent(str(spool), agent_id="wrongtype",
                            capacity={"num_devices": 8, "device_type": "cpu"})
    assert small.sweep_once() == [] and wrong_type.sweep_once() == []
    assert mgr.list_queue() == [run_id], "job must stay queued"

    big = FedMLAgent(str(spool), agent_id="big",
                     capacity={"num_devices": 8, "device_type": "tpu-v5e"})
    assert big.free_devices() == 8
    claimed = big.sweep_once()
    assert claimed == [run_id]
    assert big.free_devices() == 4  # 4 devices held while the job runs
    row = big.wait_for(run_id, timeout=60)
    assert row["status"] == "FINISHED"
    big.sweep_once()
    assert big.free_devices() == 8  # released on reap

    # all three agents registered capacity + heartbeat in the spool
    recs = {r["id"]: r for r in registered_agents(str(spool))}
    assert set(recs) == {"small", "wrongtype", "big"}
    assert recs["big"]["num_devices"] == 8


def test_cli_env_version_and_launch(tmp_path):
    from fedml_tpu import cli

    rc = cli.main(["version"])
    assert rc == 0
    job_yaml = _make_workspace(tmp_path, "print('cli-job')\n")
    spool = str(tmp_path / "spool")
    rc = cli.main(["--spool", spool, "launch", str(job_yaml)])
    assert rc == 0
    rc = cli.main(["--spool", spool, "jobs"])
    assert rc == 0


def test_cli_run_subprocess(tmp_path):
    """The reference CI pattern: run the tiny recipe via the CLI, assert exit
    code 0 (SURVEY.md §4 'smoke_test_pip_cli_sp')."""
    cfg = tmp_path / "fedml_config.yaml"
    cfg.write_text(
        "common_args:\n  federated_optimizer: FedAvg\n"
        "data_args:\n  dataset: synthetic\n  partition_method: homo\n"
        "  synthetic_train_size: 320\n  synthetic_test_size: 64\n"
        "model_args:\n  model: lr\n"
        "train_args:\n  client_num_in_total: 4\n  client_num_per_round: 2\n"
        "  comm_round: 2\n  batch_size: 16\n  learning_rate: 0.3\n"
        "device_args:\n  compute_dtype: float32\n"
        "validation_args:\n  frequency_of_the_test: 2\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "fedml_tpu.cli", "run", "--cf", str(cfg)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "test_acc" in last


def test_cli_account_model_storage_diagnosis(tmp_path, eight_devices, monkeypatch):
    """The reference CLI verb surface in self-hosted semantics (VERDICT row 1):
    login/logout, model create/list/deploy, storage, device, cluster,
    diagnosis."""
    import json as _json

    import jax
    import jax.numpy as jnp

    from fedml_tpu import cli
    from fedml_tpu.models import model_hub
    from fedml_tpu.serving.deploy import save_params_card
    from .conftest import tiny_config

    monkeypatch.setattr(cli, "_cred_path", lambda: tmp_path / "creds.json")
    spool = str(tmp_path / "spool")

    assert cli.main(["--spool", spool, "login", "alice", "--api-key", "k1"]) == 0
    assert _json.loads((tmp_path / "creds.json").read_text())["account"] == "alice"
    assert cli.main(["--spool", spool, "logout"]) == 0
    assert not (tmp_path / "creds.json").exists()

    # model registry + deploy + predict through the scheduler
    cfg = tiny_config()
    model = model_hub.create(cfg, 10)
    variables = model.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 60)), train=True)
    params = save_params_card(variables, str(tmp_path / "lr.wire"))
    assert cli.main(["--spool", spool, "model", "create", "--name", "m1",
                     "--arch", "lr", "--classes", "10", "--params", params]) == 0
    assert cli.main(["--spool", spool, "model", "list"]) == 0
    assert cli.main(["--spool", spool, "model", "deploy", "--name", "m1",
                     "--endpoint", "e1", "--timeout", "120"]) == 0

    # storage roundtrip
    src = tmp_path / "blob.bin"
    src.write_bytes(b"hello")
    assert cli.main(["--spool", spool, "storage", "upload", str(src)]) == 0
    assert cli.main(["--spool", spool, "storage", "list"]) == 0
    out = tmp_path / "blob.out"
    assert cli.main(["--spool", spool, "storage", "download", "blob.bin",
                     "--output", str(out)]) == 0
    assert out.read_bytes() == b"hello"
    assert cli.main(["--spool", spool, "storage", "delete", "blob.bin"]) == 0

    assert cli.main(["--spool", spool, "device"]) == 0
    assert cli.main(["--spool", spool, "cluster"]) == 0
    assert cli.main(["--spool", spool, "diagnosis"]) == 0


def test_cli_federate_refuses_centralized(tmp_path, eight_devices):
    from fedml_tpu import cli

    cfg_yaml = tmp_path / "central.yaml"
    cfg_yaml.write_text(
        "common_args:\n  training_type: \"centralized\"\n"
        "data_args:\n  dataset: \"synthetic\"\n  synthetic_train_size: 64\n"
        "  synthetic_test_size: 32\nmodel_args:\n  model: \"lr\"\n"
        "train_args:\n  comm_round: 1\n  batch_size: 16\n"
    )
    assert cli.main(["federate", "--cf", str(cfg_yaml)]) == 2


def test_cli_storage_refuses_traversal(tmp_path):
    from fedml_tpu import cli

    spool = str(tmp_path / "spool")
    victim = tmp_path / "spool" / "jobs.sqlite"
    victim.parent.mkdir(parents=True)
    victim.write_text("precious")
    import pytest as _pt

    with _pt.raises(SystemExit):
        cli.main(["--spool", spool, "storage", "delete", "../jobs.sqlite"])
    assert victim.exists()


def test_compress_dispatch_qsgd_int8(eight_devices):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.compression import compress

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (3000,))
    out, _ = compress("qsgd_int8", x, key=k)
    assert out.shape == x.shape
    assert float(jnp.abs(out - x).max()) < 0.2  # one int8 step per block
