"""CLI — ``python -m fedml_tpu.cli <command>``.

Parity with the reference CLI verbs (``python/fedml/cli/cli.py:11-80``):
``login``/``logout``, ``launch``, ``cluster``, ``run``, ``device``,
``model``, ``build``, ``logs``, ``train``, ``federate``, ``storage``,
``diagnosis``, ``version`` — plus ``agent``/``jobs``/``env`` from the local
scheduler.  The reference's account verbs talk to its SaaS; the self-hosted
translation keeps the same verb surface against local state: credentials in
``~/.fedml_tpu/credentials.json``, model cards + endpoints in the spool
directory's sqlite/json stores, storage as a local object dir, diagnosis as
an environment self-check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

DEFAULT_SPOOL = os.path.expanduser("~/.fedml_tpu/spool")


def cmd_run(args) -> int:
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    cfg = fedml_tpu.init(argv=["--cf", args.config] + (["--rank", str(args.rank)] if args.rank is not None else []) + (["--role", args.role] if args.role else []))
    history = FedMLRunner(cfg).run()
    if history:
        print(json.dumps(history[-1]))
    return 0


def cmd_launch(args) -> int:
    from fedml_tpu.sched.launch import FedMLLaunchManager

    mgr = FedMLLaunchManager(args.spool)
    run_id = mgr.launch_job(args.job_yaml)
    print(run_id)
    return 0


def cmd_build(args) -> int:
    from fedml_tpu.sched.launch import FedMLLaunchManager, JobSpec

    mgr = FedMLLaunchManager(args.spool)
    spec = JobSpec.from_yaml(args.job_yaml)
    pkg = mgr.build_package(spec, base_dir=str(Path(args.job_yaml).parent))
    print(pkg)
    return 0


def cmd_agent(args) -> int:
    from fedml_tpu.sched.agent import FedMLAgent

    capacity = {"num_devices": args.num_devices}
    if args.device_type:
        capacity["device_type"] = args.device_type
    if args.mem_gb:
        capacity["mem_gb"] = args.mem_gb
    agent = FedMLAgent(args.spool, agent_id=args.agent_id, capacity=capacity)
    print(f"agent watching {args.spool}", file=sys.stderr)
    try:
        agent.run_forever(poll_s=args.poll)
    except KeyboardInterrupt:
        agent.stop()
    return 0


def cmd_jobs(args) -> int:
    from fedml_tpu.sched.agent import JobDB

    db = JobDB(str(Path(args.spool) / "jobs.sqlite"))
    for row in db.all_jobs():
        print(json.dumps(row))
    return 0


def cmd_logs(args) -> int:
    from fedml_tpu.sched.agent import FedMLAgent

    print(FedMLAgent(args.spool).logs(args.run_id))
    return 0


def cmd_env(args) -> int:
    import jax

    import fedml_tpu

    info = {
        "fedml_tpu": fedml_tpu.__version__,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
    }
    print(json.dumps(info, indent=2))
    return 0


def cmd_version(args) -> int:
    import fedml_tpu

    print(fedml_tpu.__version__)
    return 0


# -- account (reference login.py/logout.py; local credentials file) ----------

def _cred_path() -> Path:
    return Path(os.path.expanduser("~/.fedml_tpu/credentials.json"))


def cmd_login(args) -> int:
    p = _cred_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    # create 0600 from the first byte — chmod-after-write leaves a window
    # where the api key is world-readable under umask 022
    fd = os.open(p, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    with os.fdopen(fd, "w") as f:
        f.write(json.dumps({"account": args.account, "api_key": args.api_key or ""}))
    # os.open's mode applies only at CREATION; tighten a pre-existing file too
    os.chmod(p, 0o600)
    print(f"logged in as {args.account}")
    return 0


def cmd_logout(args) -> int:
    p = _cred_path()
    if p.exists():
        p.unlink()
    print("logged out")
    return 0


# -- train / federate (reference train.py / federate.py job verbs) -----------

def cmd_train(args) -> int:
    """Centralized training job (reference ``fedml train``)."""
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    cfg = fedml_tpu.init(argv=["--cf", args.config])
    cfg.training_type = "centralized"
    history = FedMLRunner(cfg).run()
    if history:
        print(json.dumps(history[-1]))
    return 0


def cmd_federate(args) -> int:
    """Federated job (reference ``fedml federate``) — refuses a centralized
    recipe instead of silently running one."""
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    cfg = fedml_tpu.init(argv=["--cf", args.config])
    if cfg.training_type == "centralized":
        print("error: 'federate' needs a federated training_type "
              "(simulation/cross_silo/cross_device); use 'train' for centralized",
              file=sys.stderr)
        return 2
    history = FedMLRunner(cfg).run()
    if history:
        print(json.dumps(history[-1]))
    return 0


# -- model (reference model.py: create/list/deploy/run against the deploy
#    scheduler, local card registry in the spool) -----------------------------

def _card_registry(spool: str) -> Path:
    p = Path(spool) / "model_cards.json"
    p.parent.mkdir(parents=True, exist_ok=True)
    if not p.exists():
        p.write_text("{}")
    return p


def cmd_model(args) -> int:
    reg = _card_registry(args.spool)
    cards = json.loads(reg.read_text())
    if args.model_cmd == "create":
        cards[f"{args.name}:{args.model_version}"] = {
            "name": args.name, "version": args.model_version,
            "model": args.arch, "classes": args.classes, "params_path": args.params,
        }
        reg.write_text(json.dumps(cards, indent=2))
        print(f"registered {args.name}:{args.model_version}")
        return 0
    if args.model_cmd == "list":
        for key, card in sorted(cards.items()):
            print(json.dumps(card))
        return 0
    if args.model_cmd == "delete":
        removed = [k for k in list(cards) if k.split(":")[0] == args.name]
        for k in removed:
            del cards[k]
        reg.write_text(json.dumps(cards, indent=2))
        print(f"deleted {len(removed)} card(s)")
        return 0
    if args.model_cmd == "deploy":
        from fedml_tpu.serving.deploy import ModelCard, ModelDeployScheduler

        key = f"{args.name}:{args.model_version}"
        if key not in cards:
            print(f"error: no card {key}", file=sys.stderr)
            return 2
        sched = ModelDeployScheduler(str(Path(args.spool) / "endpoints.db"))
        sched.cards.register(ModelCard(**cards[key]))
        sched.deploy(args.endpoint, args.name, args.model_version, replicas=args.replicas)
        ok = sched.wait_ready(args.endpoint, replicas=args.replicas, timeout=args.timeout)
        ep = sched.endpoints[args.endpoint]
        print(json.dumps({"endpoint": args.endpoint, "ready": ok,
                          "ports": ep.ready_ports()}))
        if ok and args.watch:
            # foreground reconcile until interrupted — the CLI owns the
            # replica processes for the session
            sched.run_in_thread()
            try:
                import time as _t

                while True:
                    _t.sleep(1)
            except KeyboardInterrupt:
                pass
        # a one-shot CLI cannot own background processes: stop the endpoint
        # on exit either way (use --watch to keep serving)
        sched.stop()
        return 0 if ok else 1
    print(f"unknown model subcommand {args.model_cmd}", file=sys.stderr)
    return 2


# -- device / cluster (reference device.py / cluster.py; local semantics) ----

def cmd_device(args) -> int:
    import jax

    devices = [
        {"id": d.id, "kind": getattr(d, "device_kind", d.platform), "platform": d.platform}
        for d in jax.devices()
    ]
    print(json.dumps({"host_devices": devices, "process_index": jax.process_index(),
                      "process_count": jax.process_count()}, indent=2))
    return 0


def cmd_cluster(args) -> int:
    from fedml_tpu.sched.agent import JobDB

    db_path = Path(args.spool) / "jobs.sqlite"
    jobs = JobDB(str(db_path)).all_jobs() if db_path.exists() else []
    running = [j for j in jobs if j.get("status") == "RUNNING"]
    print(json.dumps({"spool": args.spool, "jobs_total": len(jobs),
                      "running": len(running)}, indent=2))
    return 0


# -- storage (reference storage.py; local object dir) ------------------------

def cmd_storage(args) -> int:
    root = (Path(args.spool) / "storage").resolve()
    root.mkdir(parents=True, exist_ok=True)

    def contained(name: str) -> Path:
        """Resolve an object name INSIDE the storage root; '..'-style
        traversal out of the object dir is refused."""
        p = (root / name).resolve()
        if not p.is_relative_to(root):
            print(f"error: object name {name!r} escapes the storage root", file=sys.stderr)
            raise SystemExit(2)
        return p

    import shutil

    if args.storage_cmd == "upload":
        src = Path(args.path)
        dest = contained(src.name)
        shutil.copyfile(src, dest)  # streaming copy — objects can be GBs
        print(str(dest))
        return 0
    if args.storage_cmd == "download":
        src = contained(args.path)
        if not src.exists():
            print(f"error: no object {args.path}", file=sys.stderr)
            return 2
        out = Path(args.output or args.path)
        shutil.copyfile(src, out)
        print(str(out))
        return 0
    if args.storage_cmd == "list":
        for p in sorted(root.iterdir()):
            print(json.dumps({"name": p.name, "bytes": p.stat().st_size}))
        return 0
    if args.storage_cmd == "delete":
        target = contained(args.path)
        if target.exists():
            target.unlink()
            print("deleted")
            return 0
        print(f"error: no object {args.path}", file=sys.stderr)
        return 2
    return 2


# -- obs (round tracing / metrics trails; ISSUE 1 observability layer) -------

def cmd_obs(args) -> int:
    """Reconstruct round timelines from collector/metrics JSONL trails
    (written by ObsCollector via extra.obs_jsonl_path, or MetricsLogger)."""
    from fedml_tpu.obs import report as obs_report

    if args.obs_cmd == "report":
        records = []
        for path in args.jsonl:
            if not Path(path).exists():
                print(f"error: no trail {path}", file=sys.stderr)
                return 2
            records.extend(obs_report.load_jsonl(path))
        if not records:
            print("error: trails contain no records", file=sys.stderr)
            return 1
        print(obs_report.render_report(records), end="")
        return 0
    if args.obs_cmd == "export":
        from fedml_tpu.obs import otlp as obs_otlp

        records = []
        for path in args.jsonl:
            if not Path(path).exists():
                print(f"error: no trail {path}", file=sys.stderr)
                return 2
            records.extend(obs_report.load_jsonl(path))
        if not records:
            print("error: trails contain no records", file=sys.stderr)
            return 1
        summary = obs_otlp.export_jsonl_trail(
            args.endpoint, records,
            batch_size=args.batch_size, timeout_s=args.timeout,
        )
        print(json.dumps(summary))
        failed = summary["spans_failed"] + summary["metric_points_failed"]
        return 0 if failed == 0 else 1
    if args.obs_cmd == "postmortem":
        from fedml_tpu.obs import postmortem as obs_postmortem

        if not Path(args.path).exists():
            print(f"error: no such path {args.path}", file=sys.stderr)
            return 2
        stitched = obs_postmortem.stitch_bundles(args.path)
        if not stitched["bundles"]:
            print(f"error: no readable flight bundles under {args.path}",
                  file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps(stitched))
        else:
            print(obs_postmortem.render_postmortem(stitched, limit=args.limit))
        # the postmortem's own verdict drives the exit code so CI can gate
        # on it: an unaccounted loss or an unattributable lost upload fails
        bad = (stitched.get("unaccounted") or 0) + \
            stitched["uploads"]["unattributed_lost"]
        return 0 if bad == 0 else 1
    if args.obs_cmd == "dash":
        from fedml_tpu.obs import dash as obs_dash
        from fedml_tpu.obs import timeline as obs_timeline

        if not Path(args.path).exists():
            print(f"error: no such path {args.path}", file=sys.stderr)
            return 2
        loaded = obs_timeline.load_timeline(args.path)
        if not loaded["samples"] and not loaded["rounds"]:
            print(f"error: no readable timeline segments under {args.path}",
                  file=sys.stderr)
            return 1
        if args.html:
            html_doc = obs_dash.render_dash_html(loaded)
            Path(args.html).write_text(html_doc)
            print(f"wrote {args.html} ({len(html_doc)} bytes)",
                  file=sys.stderr)
        print(obs_dash.render_dash_text(loaded))
        return 0
    if args.obs_cmd == "serve":
        from fedml_tpu.obs.registry import REGISTRY, MetricsHTTPServer

        server = MetricsHTTPServer(REGISTRY, port=args.port).start()
        print(f"serving /metrics and /healthz on :{server.port}", file=sys.stderr)
        try:
            import time as _t

            while True:
                _t.sleep(1)
        except KeyboardInterrupt:
            server.close()
        return 0
    print(f"unknown obs subcommand {args.obs_cmd}", file=sys.stderr)
    return 2


# -- lint (analysis/: AST invariant checker, tier-1-enforced) ----------------

def cmd_lint(args) -> int:
    """Run the GL001-GL012 static invariant rules over a package tree.

    Exit 0 = clean (counting inline suppressions and the baseline),
    1 = unsuppressed findings or unparseable files.  Deliberately imports no
    jax: the bench/dryrun drivers run this in processes that must not touch
    the accelerator runtime."""
    from fedml_tpu.analysis import engine as lint_engine
    from fedml_tpu.analysis import findings as lint_findings

    pkg_dir = Path(__file__).resolve().parent
    target = Path(args.path) if args.path else pkg_dir
    if not target.exists():
        print(f"error: no such path {target}", file=sys.stderr)
        return 2
    if args.fix:
        # --fix first rewrites the mechanical legacy idioms in place, then
        # falls through to the normal lint pass so what remains (manual
        # sites, other rules) is reported against the FIXED sources
        from fedml_tpu.analysis.fix import fix_tree

        summary = fix_tree(target)
        if args.format == "json":
            print(json.dumps({"files_changed": summary.files_changed,
                              "rewrites": summary.rewrites,
                              "manual": summary.skipped}))
        else:
            print(summary.render())
    baseline = Path(args.baseline) if args.baseline else pkg_dir / "analysis" / "baseline.json"
    result = lint_engine.run_lint(target, baseline=baseline if baseline.exists() else None)
    if args.write_baseline:
        lint_findings.save_baseline(baseline, result.findings)
        print(f"baselined {len(result.findings)} finding(s) into {baseline}")
        return 0
    if args.format == "json":
        print(json.dumps({
            "ok": result.ok,
            "findings": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "severity": f.severity, "message": f.message, "key": f.key}
                for f in result.findings
            ],
            "counts_by_rule": result.counts_by_rule(),
            "suppressed": len(result.suppressed),
            "baselined": len(result.baselined),
            "parse_errors": result.errors,
        }))
    else:
        print(result.render())
    return 0 if result.ok else 1


def cmd_diagnosis(args) -> int:
    """Reference diagnosis.py checks SaaS/MQTT/S3 connectivity; here the
    self-hosted equivalents: jax backend usable, a jit executes, the spool is
    writable, and the TCP transport can bind."""
    import socket

    checks = {}
    try:
        import jax
        import jax.numpy as jnp

        checks["jax_backend"] = jax.default_backend()
        checks["jit_executes"] = bool(jax.jit(lambda x: x + 1)(jnp.ones(8))[0] == 2.0)
    except Exception as e:
        checks["jax_error"] = f"{type(e).__name__}: {e}"
    try:
        Path(args.spool).mkdir(parents=True, exist_ok=True)
        probe = Path(args.spool) / ".diag"
        probe.write_text("ok")
        probe.unlink()
        checks["spool_writable"] = True
    except Exception as e:
        checks["spool_writable"] = f"{type(e).__name__}: {e}"
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            checks["tcp_bind"] = True
    except Exception as e:
        checks["tcp_bind"] = f"{type(e).__name__}: {e}"
    ok = checks.get("jit_executes") is True and checks.get("spool_writable") is True \
        and checks.get("tcp_bind") is True
    checks["ok"] = ok
    print(json.dumps(checks, indent=2))
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="fedml-tpu")
    parser.add_argument("--spool", default=DEFAULT_SPOOL, help="local scheduler spool dir")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("run", help="run a training recipe yaml")
    p.add_argument("--cf", dest="config", required=True)
    p.add_argument("--rank", type=int, default=None)
    p.add_argument("--role", default=None)
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("launch", help="package + submit a job.yaml")
    p.add_argument("job_yaml")
    p.set_defaults(fn=cmd_launch)

    p = sub.add_parser("build", help="build a run package without submitting")
    p.add_argument("job_yaml")
    p.set_defaults(fn=cmd_build)

    p = sub.add_parser("agent", help="start a worker agent on the spool")
    p.add_argument("--poll", type=float, default=0.5)
    p.add_argument("--agent-id", default="", help="stable agent id (default: agent_<pid>)")
    p.add_argument("--num-devices", type=int, default=1,
                   help="devices this agent offers (matched against job computing.minimum_num_gpus)")
    p.add_argument("--device-type", default="",
                   help="device type label (matched against computing.request_gpu_type)")
    p.add_argument("--mem-gb", type=float, default=0,
                   help="memory capacity in GB (0 = unlimited)")
    p.set_defaults(fn=cmd_agent)

    p = sub.add_parser("jobs", help="list job statuses")
    p.set_defaults(fn=cmd_jobs)

    p = sub.add_parser("logs", help="print a run's logs")
    p.add_argument("run_id")
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser("env", help="print environment info")
    p.set_defaults(fn=cmd_env)

    p = sub.add_parser("version", help="print version")
    p.set_defaults(fn=cmd_version)

    p = sub.add_parser("login", help="store local account credentials")
    p.add_argument("account")
    p.add_argument("--api-key", default="")
    p.set_defaults(fn=cmd_login)

    p = sub.add_parser("logout", help="remove local account credentials")
    p.set_defaults(fn=cmd_logout)

    p = sub.add_parser("train", help="run a centralized training recipe")
    p.add_argument("--cf", dest="config", required=True)
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("federate", help="run a federated recipe (refuses centralized)")
    p.add_argument("--cf", dest="config", required=True)
    p.set_defaults(fn=cmd_federate)

    p = sub.add_parser("model", help="model card registry + deploy")
    msub = p.add_subparsers(dest="model_cmd", required=True)
    mc = msub.add_parser("create")
    mc.add_argument("--name", required=True)
    mc.add_argument("--model-version", default="v1")
    mc.add_argument("--arch", required=True, help="model_hub name, e.g. lr/resnet20")
    mc.add_argument("--classes", type=int, default=10)
    mc.add_argument("--params", required=True, help="pytree-wire params file")
    ml = msub.add_parser("list")
    md = msub.add_parser("delete")
    md.add_argument("--name", required=True)
    mdep = msub.add_parser("deploy")
    mdep.add_argument("--name", required=True)
    mdep.add_argument("--model-version", default="v1")
    mdep.add_argument("--endpoint", required=True)
    mdep.add_argument("--replicas", type=int, default=1)
    mdep.add_argument("--timeout", type=float, default=60.0)
    mdep.add_argument("--watch", action="store_true")
    p.set_defaults(fn=cmd_model)

    p = sub.add_parser("device", help="show local accelerator devices")
    p.set_defaults(fn=cmd_device)

    p = sub.add_parser("cluster", help="show local cluster/agent status")
    p.set_defaults(fn=cmd_cluster)

    p = sub.add_parser("storage", help="local object storage")
    ssub = p.add_subparsers(dest="storage_cmd", required=True)
    su = ssub.add_parser("upload")
    su.add_argument("path")
    sd = ssub.add_parser("download")
    sd.add_argument("path")
    sd.add_argument("--output", default="")
    ssub.add_parser("list")
    sdel = ssub.add_parser("delete")
    sdel.add_argument("path")
    p.set_defaults(fn=cmd_storage)

    p = sub.add_parser("obs", help="observability: round timelines, metrics endpoint")
    osub = p.add_subparsers(dest="obs_cmd", required=True)
    orep = osub.add_parser("report", help="round timeline + straggler report from JSONL trails")
    orep.add_argument("jsonl", nargs="+", help="collector/metrics JSONL trail path(s)")
    oexp = osub.add_parser(
        "export", help="backfill a JSONL trail into an OTLP/HTTP collector")
    oexp.add_argument("jsonl", nargs="+", help="collector JSONL trail path(s)")
    oexp.add_argument("--endpoint", required=True,
                      help="collector base URL (POSTs /v1/traces and /v1/metrics)")
    oexp.add_argument("--batch-size", type=int, default=512)
    oexp.add_argument("--timeout", type=float, default=10.0)
    oserve = osub.add_parser("serve", help="serve /metrics + /healthz for this process")
    oserve.add_argument("--port", type=int, default=9109)
    opm = osub.add_parser(
        "postmortem",
        help="stitch flight-recorder bundles into one causal failure timeline")
    opm.add_argument("path", help="flight bundle directory (recursive) or one .flight file")
    opm.add_argument("--json", action="store_true", dest="as_json",
                     help="emit the stitched structure as JSON instead of text")
    opm.add_argument("--limit", type=int, default=40,
                     help="timeline events to render (<=0 = all; default 40)")
    odash = osub.add_parser(
        "dash",
        help="performance dashboard from recorded timeline segments")
    odash.add_argument("path",
                       help="timeline segment directory (extra.timeline_dir)")
    odash.add_argument("--html", default="",
                       help="also write a self-contained HTML dashboard here")
    p.set_defaults(fn=cmd_obs)

    p = sub.add_parser("lint", help="AST invariant checker (GL001-GL012) over fedml_tpu/")
    p.add_argument("path", nargs="?", default="",
                   help="package dir or single .py file (default: the installed fedml_tpu package)")
    p.add_argument("--baseline", default="",
                   help="suppression baseline JSON (default: fedml_tpu/analysis/baseline.json)")
    p.add_argument("--write-baseline", action="store_true",
                   help="write current findings into the baseline instead of failing")
    p.add_argument("--fix", action="store_true",
                   help="mechanically rewrite legacy extra idioms to the "
                        "registry helpers (cfg_extra / cfg_extra_present / "
                        "set_cfg_extra) before linting")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("diagnosis", help="environment/connectivity self-check")
    p.set_defaults(fn=cmd_diagnosis)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
