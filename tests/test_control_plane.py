"""Agent control-plane tests (VERDICT row 40: the MQTT start/stop/status/OTA
verbs of the reference slave agent, over the hermetic comm fabric)."""

import io
import json
import sqlite3
import time
import zipfile

import pytest

from .conftest import tiny_config


def _job_package(run_id: str, command: str) -> bytes:
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as z:
        z.writestr("__fedml_job__.json", json.dumps({"run_id": run_id, "job": command}))
    return buf.getvalue()


def _wait_spooled(agent, run_id, timeout=30.0):
    """Until the START_RUN handler is done with ``run_id``: it writes the
    package (``write_bytes``, not atomic) and THEN the QUEUED row, so the row
    is what says the package is whole.  A sweep started on the file's mere
    appearance can meet half a zip, or race the handler's upsert of the row."""
    import time

    deadline = time.time() + timeout
    while agent.db.get(run_id) is None and time.time() < deadline:
        time.sleep(0.05)
    assert agent.db.get(run_id) is not None, f"{run_id} never spooled"


@pytest.mark.xfail(strict=True, raises=sqlite3.IntegrityError,
                   reason="sched/agent.py JobDB.upsert is SELECT-then-INSERT: the START_RUN handler's first "
                          "write of a run and a sweep's can both find no row and both INSERT.  The waits "
                          "above keep this file's tests clear of it; the defect is a sched/ issue's to mend "
                          "(INSERT OR IGNORE, and an atomic rename of the package), which then drops this mark")
def test_jobdb_upsert_survives_another_writers_first_row(tmp_path):
    """Two writers' first ``upsert`` of one run, interleaved as the control
    plane's handler thread and the agent's sweep can be: the second finds no
    row, the first INSERTs, the second INSERTs too."""
    from fedml_tpu.sched.agent import JobDB

    db = JobDB(str(tmp_path / "jobs.sqlite"))
    other = JobDB(db.path)

    class Interleaved(sqlite3.Connection):
        def execute(self, sql, *args):
            if sql.startswith("INSERT"):  # between this writer's SELECT and its INSERT
                other.upsert("job-x", status="QUEUED")
            return super().execute(sql, *args)

    db._conn = lambda: sqlite3.connect(db.path, factory=Interleaved)
    db.upsert("job-x", status="PROVISIONING")
    assert db.get("job-x")["status"] == "PROVISIONING"


def test_control_plane_start_status_stop_ota(tmp_path, eight_devices):
    import fedml_tpu
    from fedml_tpu.comm.inproc import InProcRouter
    from fedml_tpu.sched.agent import FedMLAgent
    from fedml_tpu.sched.control_plane import AgentControlPlane, AgentController

    cfg = tiny_config(run_id="cp1", backend="INPROC")
    fedml_tpu.init(cfg)
    InProcRouter.reset("cp1")

    agent = FedMLAgent(str(tmp_path / "spool"))
    plane = AgentControlPlane(cfg, agent, rank=7, backend="INPROC")
    plane.run_in_thread()
    controller = AgentController(cfg, backend="INPROC")
    controller.run_in_thread()
    try:
        # START_RUN -> package lands in the queue -> agent sweep claims it
        controller.start_run(7, "job-1", _job_package("job-1", "echo control-plane-ok"))
        deadline = time.time() + 30
        while not list(agent.queue.glob("*.zip")) and time.time() < deadline:
            time.sleep(0.05)
        assert list(agent.queue.glob("*.zip")), "package never spooled"
        _wait_spooled(agent, "job-1")
        agent.sweep_once()
        deadline = time.time() + 60
        while agent._procs and time.time() < deadline:
            agent.sweep_once()
            time.sleep(0.1)
        row = agent.db.get("job-1")
        assert row["status"] == "FINISHED", row

        # STATUS round trip
        controller.request_status(7)
        jobs = controller.wait_status(7, timeout=30)
        assert jobs is not None and any(j["run_id"] == "job-1" for j in jobs)

        # STOP_RUN on a long-running job
        controller.start_run(7, "job-2", _job_package("job-2", "sleep 60"))
        deadline = time.time() + 30
        while not list(agent.queue.glob("*.zip")) and time.time() < deadline:
            time.sleep(0.05)
        _wait_spooled(agent, "job-2")
        agent.sweep_once()
        assert "job-2" in agent._procs
        controller.stop_run(7, "job-2")
        # wait on the DB row, not the process table: the handler pops the
        # proc BEFORE it writes KILLED, so polling _procs races the upsert
        deadline = time.time() + 45
        while agent.db.get("job-2")["status"] != "KILLED" and time.time() < deadline:
            time.sleep(0.1)
        assert agent.db.get("job-2")["status"] == "KILLED"
        assert agent._procs.get("job-2") is None

        # OTA stages the package + restart marker
        controller.push_ota(7, "0.2.0", b"new-agent-code")
        deadline = time.time() + 30
        marker = tmp_path / "spool" / "ota" / "RESTART_REQUIRED"
        while not marker.exists() and time.time() < deadline:
            time.sleep(0.05)
        assert marker.exists()
        meta = json.loads(marker.read_text())
        assert meta["version"] == "0.2.0"
        assert (tmp_path / "spool" / "ota" / "agent-0.2.0.zip").read_bytes() == b"new-agent-code"
    finally:
        plane.finish()
        controller.finish()


def test_control_plane_rejects_traversal_and_stop_races(tmp_path, eight_devices):
    import time

    import fedml_tpu
    from fedml_tpu.comm.inproc import InProcRouter
    from fedml_tpu.sched.agent import FedMLAgent
    from fedml_tpu.sched.control_plane import AgentControlPlane, AgentController

    cfg = tiny_config(run_id="cp2", backend="INPROC")
    fedml_tpu.init(cfg)
    InProcRouter.reset("cp2")
    agent = FedMLAgent(str(tmp_path / "spool"))
    plane = AgentControlPlane(cfg, agent, rank=3, backend="INPROC")
    plane.run_in_thread()
    controller = AgentController(cfg, backend="INPROC")
    try:
        # traversal run_id must never land outside the queue
        controller.start_run(3, "../../evil", _job_package("x", "echo hi"))
        time.sleep(0.5)
        assert not (tmp_path / "evil.zip").exists()
        assert not list(agent.queue.glob("*.zip"))

        # stop-before-start: queued package must be removed, job never runs
        controller.start_run(3, "job-r", _job_package("job-r", "echo nope"))
        deadline = time.time() + 30
        while not list(agent.queue.glob("*.zip")) and time.time() < deadline:
            time.sleep(0.05)
        _wait_spooled(agent, "job-r")
        controller.stop_run(3, "job-r")
        deadline = time.time() + 30
        while list(agent.queue.glob("*.zip")) and time.time() < deadline:
            time.sleep(0.05)
        assert not list(agent.queue.glob("*.zip"))
        agent.sweep_once()
        # the STOP_RUN handler unlinks the package BEFORE it writes KILLED: wait on the row
        deadline = time.time() + 30
        while agent.db.get("job-r")["status"] != "KILLED" and time.time() < deadline:
            time.sleep(0.05)
        assert agent.db.get("job-r")["status"] == "KILLED"
        assert "job-r" not in agent._procs
    finally:
        plane.finish()
        controller.finish()


def test_control_plane_package_auth(tmp_path, eight_devices):
    """START_RUN/OTA are code execution on the agent: with a configured
    shared secret a bad/absent HMAC must be rejected, a good one accepted;
    without a secret, routable backends must refuse package verbs outright."""
    import fedml_tpu
    from fedml_tpu.comm.inproc import InProcRouter
    from fedml_tpu.comm.message import Message
    from fedml_tpu.sched.agent import FedMLAgent
    from fedml_tpu.sched.control_plane import (
        KEY_PACKAGE, KEY_RUN_ID, KEY_SIGNATURE, KEY_TIMESTAMP,
        MSG_TYPE_START_RUN, MSG_TYPE_STOP_RUN,
        AgentControlPlane, AgentController, _verb_signature,
    )

    cfg = tiny_config(run_id="cp3", backend="INPROC")
    cfg.control_plane_secret = "sesame"
    fedml_tpu.init(cfg)
    InProcRouter.reset("cp3")
    agent = FedMLAgent(str(tmp_path / "spool"))
    plane = AgentControlPlane(cfg, agent, rank=5, backend="INPROC")
    plane.run_in_thread()
    controller = AgentController(cfg, backend="INPROC")
    try:
        import numpy as np

        pkg = _job_package("job-a", "echo authed")

        # forged signature (fresh timestamp): package must never hit the spool
        msg = Message(MSG_TYPE_START_RUN, 0, 5)
        msg.add_params(KEY_PACKAGE, np.frombuffer(pkg, dtype=np.uint8).copy())
        msg.add_params(KEY_RUN_ID, "job-a")
        msg.add_params(KEY_TIMESTAMP, repr(time.time()))
        msg.add_params(KEY_SIGNATURE, "0" * 64)
        controller.send_message(msg)
        time.sleep(0.5)
        assert not list(agent.queue.glob("*.zip")), "forged package spooled"

        # stale-but-correctly-signed (replay): rejected by the freshness window
        old_ts = repr(time.time() - 3600)
        replay = Message(MSG_TYPE_START_RUN, 0, 5)
        replay.add_params(KEY_PACKAGE, np.frombuffer(pkg, dtype=np.uint8).copy())
        replay.add_params(KEY_RUN_ID, "job-a")
        replay.add_params(KEY_TIMESTAMP, old_ts)
        replay.add_params(
            KEY_SIGNATURE, _verb_signature("sesame", MSG_TYPE_START_RUN, 5, "job-a", old_ts, pkg)
        )
        controller.send_message(replay)
        time.sleep(0.5)
        assert not list(agent.queue.glob("*.zip")), "replayed package spooled"

        # unsigned STOP_RUN must not kill jobs when a secret is configured
        agent.db.upsert("job-x", status="RUNNING")
        bare_stop = Message(MSG_TYPE_STOP_RUN, 0, 5)
        bare_stop.add_params(KEY_RUN_ID, "job-x")
        controller.send_message(bare_stop)
        time.sleep(0.5)
        assert agent.db.get("job-x")["status"] == "RUNNING"

        # correctly signed (controller signs automatically with the secret)
        controller.start_run(5, "job-a", pkg)
        deadline = time.time() + 30
        while not list(agent.queue.glob("*.zip")) and time.time() < deadline:
            time.sleep(0.05)
        assert list(agent.queue.glob("*.zip")), "signed package rejected"

        # signed STOP_RUN works
        controller.stop_run(5, "job-x")
        deadline = time.time() + 30
        while agent.db.get("job-x")["status"] != "KILLED" and time.time() < deadline:
            time.sleep(0.05)
        assert agent.db.get("job-x")["status"] == "KILLED"

        # signature is verb/name-bound
        s1 = _verb_signature("sesame", MSG_TYPE_START_RUN, 5, "job-a", "1.0", pkg)
        s2 = _verb_signature("sesame", MSG_TYPE_START_RUN, 5, "job-b", "1.0", pkg)
        assert s1 != s2

        # unauthenticated plane on a routable backend refuses packages
        # (backend attribute faked to avoid binding a real TCP socket)
        plane_open = AgentControlPlane(
            tiny_config(run_id="cp3b", backend="INPROC"), agent, rank=6, backend="INPROC"
        )
        plane_open.secret = None
        plane_open.backend = "TCP"
        import pytest

        with pytest.raises(ValueError, match="unauthenticated"):
            plane_open._verify(msg, MSG_TYPE_START_RUN, "job-a", pkg)
        plane_open.finish()
    finally:
        plane.finish()
        controller.finish()
