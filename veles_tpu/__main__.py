"""CLI entry point: ``python -m veles_tpu <config> [options] [overrides]``.

Reference parity: veles/__main__.py (``Main`` :136) — positional workflow +
config files, ``--optimize N[:G]`` GA mode (:716-734), ``--ensemble-train
N:r`` / ``--ensemble-test``, ``--dump-config``, ``--result-file``,
``--random-seed`` (:483-537), snapshot-restore positional (:539-589),
``--dry-run`` levels, inline ``root.x.y=z`` overrides (:474-481).
Subcommands: ``benchmark`` (device gemm DB), ``forge`` (model store),
``compare-snapshots A B`` (per-tensor checkpoint diff — reference:
veles/scripts/compare_snapshots.py).

Config conventions (TPU-native redesign of "user config files are executed
Python mutating root", veles/__main__.py:426-472):

* ``config.py``  — executed with ``root`` bound; must define
  ``create(root) -> veles_tpu.Trainer`` (full control), OR set
  ``root.workflow`` / ``root.loader`` trees for the standard path.
* ``config.json`` — merged into ``root``; must contain ``workflow``
  (StandardWorkflow layer config) and ``loader`` ({"name": ..., args}).

Named loaders: mnist, cifar, imagenet_synthetic.
"""

from __future__ import annotations

import argparse
import json
import runpy
import sys
from typing import Optional

from . import prng
from .config import Config, apply_overrides, root
from .logger import setup_logging
from .runtime import Decision, Snapshotter, Trainer


LOADERS = {
    "mnist": "veles_tpu.models.mnist:MnistLoader",
    "cifar": "veles_tpu.models.cifar:CifarLoader",
    "stl": "veles_tpu.models.stl:StlLoader",
    "induction": "veles_tpu.models.lm:InductionLoader",
    "imagenet_synthetic":
        "veles_tpu.models.alexnet:ImagenetSyntheticLoader",
}


def make_loader(name: str, **args):
    import importlib
    mod, _, attr = LOADERS[name].partition(":")
    return getattr(importlib.import_module(mod), attr)(**args)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu",
        description="TPU-native deep learning framework "
                    "(Veles-capability rebuild)")
    p.add_argument("config", nargs="?",
                   help="config .py/.json, or a snapshot .json manifest "
                        "to resume")
    p.add_argument("overrides", nargs="*", default=[],
                   help="inline config overrides: path.to.key=value")
    p.add_argument("--snapshot",
                   help="snapshot manifest to restore from: a file path, "
                        "sqlite://db#id, or http(s):// manifest URL")
    p.add_argument("--visualize", metavar="PATH",
                   help="write the workflow DOT graph here (and PATH.svg "
                        "when graphviz is installed), then continue "
                        "(reference: veles --visualize)")
    p.add_argument("--background", action="store_true",
                   help="daemonize: detach from the terminal and keep "
                        "training (reference: veles --background); logs "
                        "go to --background-log")
    p.add_argument("--background-log", default="veles_tpu.log",
                   help="log file for --background mode")
    p.add_argument("--random-seed", default=None,
                   help="int, hex (0x...), or a file whose bytes seed the "
                        "generators (reference: veles/__main__.py:483-537 "
                        "accepted hex strings and /dev/urandom-style "
                        "sources)")
    p.add_argument("--dump-config", action="store_true")
    p.add_argument("--dry-run", choices=["init", "build"], default=None,
                   help="stop after loader init / workflow build")
    p.add_argument("--result-file", help="write results JSON here")
    p.add_argument("--optimize", metavar="N[:G]",
                   help="GA over config Range tuneables: population[:gens]")
    p.add_argument("--workers", type=int, default=1,
                   help="parallel evaluation workers for --optimize / "
                        "--ensemble-train: each evaluation runs as a "
                        "standalone CLI subprocess on a pool this size "
                        "(reference: slave farm-out). Workers default to "
                        "CPU (JAX_PLATFORMS=cpu) so they don't fight over "
                        "one TPU chip")
    p.add_argument("--ensemble-train", metavar="N:r",
                   help="train N members on ratio-r subsets")
    p.add_argument("--ensemble-test", metavar="MANIFEST",
                   help="test an ensemble from its manifest JSON")
    p.add_argument("--curriculum", metavar="SPEC.json",
                   help="snapshot-phased curriculum: run the config as "
                        "chained training phases per the spec (each "
                        "phase restores the best snapshot so far; see "
                        "runtime/curriculum.py)")
    p.add_argument("--curriculum-out", default="curriculum_out",
                   help="directory for per-phase snapshots/results")
    p.add_argument("--mesh", help="mesh spec, e.g. data=4,model=2")
    p.add_argument("--compile-cache", metavar="DIR", default=None,
                   help="persistent XLA compilation cache directory "
                        "(root.common.compile_cache): restarted runs "
                        "with unchanged step programs skip the backend "
                        "compile entirely; see docs/compile_cache.md")
    p.add_argument("--platform", default=None,
                   help="pin the jax platform (cpu/tpu) before first "
                        "backend use — the flag form of JAX_PLATFORMS, "
                        "forwarded to curriculum phases; '--platform "
                        "cpu' with XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=N gives a virtual-device mesh")
    p.add_argument("--hosts",
                   help="comma-separated hosts: respawn this command on "
                        "each via ssh (localhost entries spawn locally) "
                        "as one SPMD gang (reference: -n slave specs)")
    p.add_argument("--max-epochs", type=int, default=None)
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--frontend", action="store_true",
                   help="serve a browser form that composes this command "
                        "line (reference: veles --frontend)")
    p.add_argument("--publish", metavar="DIR[:FMT]",
                   help="after training, write a run report to DIR; FMT "
                        "is markdown (default), html or pdf — comma-"
                        "separate for several (reference: the Publisher "
                        "unit, veles/publishing/publisher.py:57)")
    p.add_argument("--profile-units", action="store_true",
                   help="before training, time each unit's apply with a "
                        "forced device sync and print the top-5 table "
                        "(reference: --sync-run honest per-unit timers + "
                        "Workflow.print_stats)")
    p.add_argument("--export", metavar="DIR[.zip]", default=None,
                   help="write a native-serving package of the "
                        "(restored) model and exit — contents.json + "
                        "npy for veles_serve (reference: "
                        "Workflow.package_export, veles/workflow.py:868)")
    p.add_argument("--compiled", action="store_true",
                   help="with --export DIR: write a sealed compiled "
                        "artifact instead (jax.export StableHLO of the "
                        "batched forward + the decode engine's fixed "
                        "program set, manifest, weights blob) and print "
                        "the manifest summary; serve it with "
                        "--serve --artifact DIR "
                        "(docs/serving_export.md)")
    p.add_argument("--artifact", metavar="DIR", default=None,
                   help="with --serve: boot from a compiled artifact "
                        "directory (export_compiled) — deserialized "
                        "StableHLO programs, zero model Python, no "
                        "config file needed")
    p.add_argument("--generate", type=int, metavar="N", default=None,
                   help="decode N tokens after --prompt with the "
                        "(restored) sequence model instead of training "
                        "— KV-cached greedy/temperature sampling "
                        "(veles_tpu.generate); prints the token rows "
                        "as JSON")
    p.add_argument("--prompt", default=None,
                   help="comma-separated token ids for --generate "
                        "(';' separates batch rows), or @file.npy")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature for --generate "
                        "(0 = greedy)")
    p.add_argument("--top-k", type=int, default=None,
                   help="restrict --generate sampling to the k highest "
                        "logits (needs --temperature > 0)")
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling for --generate: smallest "
                        "token set with cumulative probability >= p "
                        "(needs --temperature > 0)")
    p.add_argument("--beams", type=int, default=1,
                   help="beam-search width for --generate (>1 returns "
                        "the highest-total-log-prob continuation; "
                        "exclusive with sampling flags)")
    p.add_argument("--eos-id", type=int, default=None,
                   help="end-of-sequence token: greedy/sampling decode "
                        "stops a row that emits it (padding the rest); "
                        "with --beams, finished beams freeze and pad")
    p.add_argument("--length-penalty", type=float, default=0.0,
                   help="beam score normalization exponent over the "
                        "generated length (GNMT convention; 0 = raw "
                        "log-prob sum)")
    p.add_argument("--serve", type=int, metavar="PORT", default=None,
                   help="serve the (restored) model over HTTP instead "
                        "of training: POST /predict, plus POST "
                        "/generate for sequence chains "
                        "(runtime/restful.py; 0 = ephemeral port); "
                        "blocks until drained (SIGTERM / POST "
                        "/admin/drain) or interrupted")
    p.add_argument("--fleet", type=int, metavar="N", default=None,
                   help="with --serve: boot N replica serving stacks "
                        "in this process behind the fleet router "
                        "(load- + prefix-affinity dispatch, "
                        "coordinated hot swap, rolling drain — "
                        "docs/serving.md 'Fleet serving'); PORT "
                        "serves the router, replicas take ephemeral "
                        "ports (default root.common.serve.fleet."
                        "replicas)")
    p.add_argument("--join", metavar="ROUTER_URL", default=None,
                   help="with --serve: register this replica with a "
                        "running fleet router after boot (POST "
                        "/admin/join) so it starts receiving "
                        "dispatched traffic; the router drains it "
                        "during a rolling drain and readmits it on "
                        "/ready")
    p.add_argument("--model-dir", default=None,
                   help="snapshot directory backing --serve's model "
                        "lifecycle control plane (runtime/deploy.py): "
                        "POST /admin/reload hot-swaps a snapshot/"
                        "package with zero downtime, GET /models lists "
                        "the versioned registry")
    p.add_argument("--watch", action="store_true",
                   help="with --serve --model-dir: poll the directory "
                        "for newer snapshots and hot-swap them "
                        "automatically (exponential retry backoff on "
                        "failures)")
    p.add_argument("--drain-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="graceful-drain deadline for SIGTERM / POST "
                        "/admin/drain: admissions stop and /ready "
                        "answers 503 immediately, in-flight work gets "
                        "this long to retire (default "
                        "root.common.serve.drain_timeout_s)")
    p.add_argument("--status-port", type=int, default=None,
                   help="serve a live status page (JSON + HTML with "
                        "auto-refreshing metric plots) on this port; 0 "
                        "picks a free port (reference: the Tornado web "
                        "status + WebAgg live plots, veles/web_status.py)")
    p.add_argument("--plots", metavar="DIR", default=None,
                   help="write metric-curve PNGs/JSONL here each epoch "
                        "(default 'plots' when --status-port is set)")
    p.add_argument("--trace-out", metavar="FILE", default=None,
                   help="on exit, write the host-side span timeline "
                        "(per-request queue-wait/prefill/decode spans, "
                        "training epochs, status events) as Chrome-"
                        "trace JSON — open in Perfetto; the same "
                        "document GET /trace.json serves live")
    p.add_argument("--profile", metavar="DIR",
                   help="capture a device-level jax.profiler trace of the "
                        "training run into DIR (view with TensorBoard / "
                        "xprof; complements the host-side EventTracer "
                        "timeline)")
    p.add_argument("--verbose", "-v", action="store_true")
    p.add_argument("--version", action="version",
                   version=f"veles_tpu {_version()}")
    p.add_argument("--list-units", action="store_true",
                   help="print the registered unit classes and exit")
    return p


def _version() -> str:
    from . import __version__
    return __version__


def _make_trainer_from_root(cfg: Config, args) -> Trainer:
    """The standard path: root.workflow + root.loader trees."""
    from .models.standard import StandardWorkflow
    wf_cfg = cfg.workflow.to_dict() if "workflow" in cfg else None
    if not wf_cfg:
        raise SystemExit("config must define root.workflow (layer list) "
                         "or a create(root) function")
    sw = StandardWorkflow(wf_cfg)
    loader_cfg = cfg.loader.to_dict() if "loader" in cfg else {}
    name = loader_cfg.pop("name", "mnist")
    loader = make_loader(name, **loader_cfg)
    decision = Decision(
        max_epochs=args.max_epochs or wf_cfg.get("max_epochs"),
        fail_iterations=wf_cfg.get("fail_iterations", 50))
    snap = None
    if args.snapshot_dir:
        snap = Snapshotter(wf_cfg.get("name", "workflow"),
                           args.snapshot_dir)
    mesh = _make_mesh(args.mesh)
    rule = mesh_rule(sw.workflow, mesh) if mesh is not None else None
    return Trainer(sw.workflow, loader, sw.optimizer, decision, snap,
                   mesh=mesh, rule=rule,
                   pipeline_microbatches=wf_cfg.get(
                       "pipeline_microbatches"),
                   pipeline_interleave=wf_cfg.get(
                       "pipeline_interleave", 1))


def mesh_rule(workflow, mesh):
    """The sharding rule ``--mesh`` composes for the parallel units
    present in the graph: expert banks on 'expert', pipeline stages on
    'pipe', large parameters on 'fsdp'; None when no axis asks for one
    (pure data parallelism).  A 'model' axis gets no rule here: tensor
    parallelism needs an explicit ``tensor_parallel_rules`` table."""
    from .parallel.mesh import compose_rules, fsdp_rules
    from .units.parallel_nn import (MoEFFN, PipelineStack, expert_rules,
                                    pipeline_rules)
    rules = []
    kinds = {type(u) for u in workflow.units}
    if MoEFFN in kinds and mesh.shape.get("expert", 1) > 1:
        rules.append(expert_rules())
    if PipelineStack in kinds and mesh.shape.get("pipe", 1) > 1:
        rules.append(pipeline_rules())
    if mesh.shape.get("fsdp", 1) > 1:
        rules.append(fsdp_rules(axis_size=mesh.shape["fsdp"]))
    return compose_rules(*rules) if rules else None


def _make_mesh(spec: Optional[str]):
    if not spec:
        return None
    from .parallel import MeshSpec, make_mesh
    kw = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        kw[k.strip()] = int(v)
    return make_mesh(MeshSpec(**kw))


def _load_config(path: str, overrides):
    """Returns (create_fn_or_None, snapshot_manifest_or_None).

    A positional .json that is actually a snapshot manifest (has a
    'tensors' key — see Snapshotter.save) restores config from its
    embedded 'config' snapshot and schedules a state restore (reference:
    positional snapshot restore, veles/__main__.py:539-589)."""
    create, snapshot = None, None
    if path.endswith(".json"):
        with open(path) as f:
            data = json.load(f)
        if "tensors" in data:  # snapshot manifest, not a config
            snapshot = path
            root.update(data.get("config", {}))
        else:
            root.update(data)
    else:
        ns = runpy.run_path(path, init_globals={"root": root})
        create = ns.get("create")
    apply_overrides(root, overrides)
    return create, snapshot


def _forge_main(argv) -> int:
    """``python -m veles_tpu forge <action>`` (reference: the ``veles forge``
    subcommand, veles/__main__.py:217 _process_special_args +
    veles/forge/forge_client.py ACTIONS)."""
    p = argparse.ArgumentParser(prog="veles_tpu forge")
    sub = p.add_subparsers(dest="action", required=True)
    for act in ("list", "details", "delete"):
        sp = sub.add_parser(act)
        sp.add_argument("--server", "-s", required=True)
        if act != "list":
            sp.add_argument("name")
    sp = sub.add_parser("fetch")
    sp.add_argument("--server", "-s", required=True)
    sp.add_argument("name")
    sp.add_argument("dest")
    sp.add_argument("--version", default=None)
    sp = sub.add_parser("upload")
    sp.add_argument("--server", "-s", required=True)
    sp.add_argument("path")
    sp.add_argument("--manifest", "-m",
                    help="manifest JSON file (default <path>/manifest.json)")
    sp = sub.add_parser("serve")
    sp.add_argument("store_dir")
    sp.add_argument("--port", type=int, default=8080)
    a = p.parse_args(argv)

    from .forge import ForgeClient, ForgeServer, ForgeStore
    if a.action == "serve":
        srv = ForgeServer(ForgeStore(a.store_dir), port=a.port).start()
        try:
            srv._thread.join()
        except KeyboardInterrupt:
            srv.stop()
        return 0
    client = ForgeClient(a.server)
    if a.action == "list":
        print(json.dumps(client.list(), indent=1))
    elif a.action == "details":
        print(json.dumps(client.details(a.name), indent=1))
    elif a.action == "delete":
        client.delete(a.name)
    elif a.action == "fetch":
        client.fetch(a.name, a.dest, a.version)
    elif a.action == "upload":
        import os
        mpath = a.manifest or os.path.join(a.path, "manifest.json")
        with open(mpath) as f:
            print(json.dumps(client.upload(a.path, json.load(f))))
    return 0


def _parse_seed(s: str) -> int:
    """int, 0x-hex, or a file/device whose first 8 bytes seed things."""
    import os
    try:
        return int(s, 10)
    except ValueError:
        pass
    if s.lower().startswith("0x"):
        try:
            return int(s, 16)
        except ValueError:
            raise SystemExit(f"--random-seed {s!r}: bad hex literal")
    if os.path.exists(s):  # regular file OR char device (/dev/urandom)
        with open(s, "rb") as f:
            data = f.read(8)
        if not data:
            raise SystemExit(f"seed file {s!r} is empty")
        return int.from_bytes(data, "little")
    raise SystemExit(
        f"--random-seed {s!r}: not an int, 0x-hex, or readable file")


_PUBLISH_FORMATS = ("markdown", "html", "pdf")


def _publish_backends():
    from .publishing import HtmlBackend, MarkdownBackend, PdfBackend
    return {"markdown": MarkdownBackend, "html": HtmlBackend,
            "pdf": PdfBackend}


def _publish_fmts(fmts: str):
    out = [f.strip() for f in (fmts or "markdown").split(",")]
    bad = [f for f in out if f not in _PUBLISH_FORMATS]
    if bad:
        raise SystemExit(
            f"unknown --publish format(s) {bad}; "
            f"choose from {', '.join(_PUBLISH_FORMATS)}")
    return out


def _daemonize(log_path: str) -> int:
    """Double-fork daemonization. Returns the daemon pid in the original
    process, 0 in the daemon (which has stdio redirected to ``log_path``),
    -1 if the intermediate child died before reporting a pid."""
    import os
    r, w = os.pipe()
    pid = os.fork()
    if pid > 0:  # original process
        os.close(w)
        data = os.read(r, 32)
        os.close(r)
        os.waitpid(pid, 0)
        return int(data) if data else -1
    os.close(r)
    os.setsid()
    pid2 = os.fork()
    if pid2 > 0:  # session leader: report the grandchild and vanish
        os.write(w, str(pid2).encode())
        os._exit(0)
    os.close(w)
    os.environ["VELES_DAEMONIZED"] = "1"
    fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    null = os.open(os.devnull, os.O_RDONLY)
    os.dup2(null, 0)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    os.close(fd)
    os.close(null)
    return 0


def _write_graph(workflow, path: str) -> None:
    """Dump the workflow DOT (reference: --visualize rendered the graph;
    here it lands as files: PATH and PATH.svg — rendered by graphviz
    when available, else by the native Workflow.generate_svg layout)."""
    with open(path, "w") as f:
        f.write(workflow.generate_graph())
    import shutil
    import subprocess
    if shutil.which("dot"):
        subprocess.run(["dot", "-Tsvg", path, "-o", path + ".svg"],
                       check=False)
    else:
        with open(path + ".svg", "w") as f:
            f.write(workflow.generate_svg())


def _check_watch(args) -> None:
    """``--watch`` needs a directory to poll — ONE check, called early
    by ``_serve_artifact`` (before the expensive boot) and again by the
    shared serve loop."""
    if args.watch and not (args.model_dir
                           or root.common.serve.get("model_dir")):
        raise SystemExit("--watch needs --model-dir (the snapshot "
                         "directory to poll)")


def _fleet_n(args) -> int:
    """Replica count for ``--serve --fleet``: the flag wins, the
    ``root.common.serve.fleet.replicas`` knob backs it (0 = plain
    single-replica serving, no router)."""
    if args.fleet is not None:
        return max(0, int(args.fleet))
    return max(0, int(root.common.serve.fleet.get("replicas", 0) or 0))


def _serve_fleet(args, factory, banner: dict) -> int:
    """``--serve PORT --fleet N``: N in-process replica serving stacks
    (each built by ``factory`` — a zero-arg callable returning a
    STARTED RestfulServer with its DeployController attached) fronted
    by the fleet router (runtime/fleet.py).  PORT serves the router;
    replicas listen on ephemeral local ports.  Blocks until the fleet
    drains (SIGTERM / POST /admin/drain on the router)."""
    from .runtime.fleet import FleetRouter, FleetServer, InProcessReplica

    if args.watch:
        raise SystemExit(
            "--watch is per-replica and conflicts with --fleet: "
            "fleet-wide version changes go through the router's "
            "coordinated swap (POST /admin/reload on the router)")
    if args.join:
        raise SystemExit("--fleet runs the router; --join makes this "
                         "process a replica of ANOTHER router — "
                         "pick one")
    n = _fleet_n(args)
    replicas = [InProcessReplica(factory) for _ in range(n)]
    router = FleetRouter()
    for rep in replicas:
        # one process = one metrics registry: the SLO merge must count
        # the shared histograms once, not per replica
        router.add_replica(url=rep.url, registry_key="in-process",
                           restart=rep.restart, kill=rep.kill)
    fsrv = FleetServer(router, port=args.serve)
    fsrv.install_signal_handlers()
    fsrv.start()
    print(json.dumps(dict(
        banner, fleet=n, serving=fsrv.port,
        replicas=[r.url for r in replicas],
        observe=["/metrics", "/fleet.json", "/slo.json"])), flush=True)
    try:
        router.wait()  # released by SIGTERM / POST /admin/drain
    except KeyboardInterrupt:
        router.begin_drain()
    fsrv.stop()
    for rep in replicas:
        rep.stop()
    _maybe_write_trace(args)
    return 0


def _run_serve_loop(args, srv, banner: dict, *, status=None,
                    boot_source: str = "live") -> int:
    """The ONE serve bootstrap/teardown config-booted (``--serve``) and
    artifact-booted (``--serve --artifact``) serving share: deploy
    control plane, signal handlers, optional snapshot watcher, JSON
    boot banner, then block until drained."""
    from .runtime.deploy import DeployController

    _check_watch(args)
    deploy = DeployController(
        server=srv, model_dir=args.model_dir,
        drain_timeout_s=args.drain_timeout,
        status=status, boot_source=boot_source)
    deploy.install_signal_handlers()
    srv.start()
    if args.join:
        # replica mode: hand this process's serving URL to a running
        # fleet router; retries ride the shared transient-HTTP backoff
        # (the router may still be booting)
        from .runtime.deploy import http_retry
        from .runtime.fleet_client import ReplicaClient, ReplicaUnavailable

        def _join():
            try:
                return ReplicaClient(args.join).request(
                    "POST", "/admin/join",
                    {"url": f"http://127.0.0.1:{srv.port}"})
            except ReplicaUnavailable as e:
                # surface as the transport error http_retry retries
                raise ConnectionError(str(e)) from e

        status_code, _h, doc = http_retry(_join, what="fleet join")
        if status_code != 200:
            srv.stop()
            raise SystemExit(
                f"--join {args.join}: router refused the replica "
                f"(HTTP {status_code}: {doc})")
    if args.watch:
        deploy.start_watcher()
    print(json.dumps(dict(banner, serving=srv.port,
                          model_dir=deploy.model_dir,
                          watching=deploy.watching,
                          # the deep-observability surface riding every
                          # serve boot (docs/observability.md)
                          observe=["/metrics", "/trace.json",
                                   "/slo.json", "/memory.json",
                                   "/debug/profile"])), flush=True)
    try:
        deploy.wait()  # released by SIGTERM / POST /admin/drain
    except KeyboardInterrupt:
        deploy.drain(timeout=0)  # interactive: skip the grace hold
    srv.stop()
    _maybe_write_trace(args)
    return 0


def _maybe_write_trace(args) -> None:
    """``--trace-out FILE``: dump the span ring (request timelines /
    train epochs / status events) as a Perfetto-loadable Chrome trace
    at shutdown."""
    if getattr(args, "trace_out", None):
        from .runtime.metrics import write_chrome_trace
        write_chrome_trace(args.trace_out)


def _serve_artifact(args) -> int:
    """``--serve --artifact DIR``: boot HTTP serving from a sealed
    compiled artifact (export_compiled) — deserialized StableHLO
    programs + weights blob, no model Python config anywhere.  Decodable
    artifacts serve POST /generate through an ArtifactRunner (the
    continuous-batching engine over the sealed program set); the
    exported batched forward backs POST /predict.  The deploy control
    plane wraps it exactly like config-booted serving: /models,
    /admin/reload (snapshots, packages, other artifacts — weights only,
    programs stay sealed), graceful drain."""
    import numpy as np

    from .runtime.artifact import (ArtifactRunner, load_forward,
                                   read_manifest)
    from .runtime.restful import RestfulServer

    _check_watch(args)  # fail BEFORE the expensive artifact boot
    man = read_manifest(args.artifact)

    def build_server(port):
        runner = None
        if "decode" in man.get("programs", {}):
            runner = ArtifactRunner(args.artifact)
            wstate = runner.wstate
            predict_fn = runner.predict if runner.has_forward else None
        else:
            predict_fn, wstate, _m = load_forward(args.artifact)

        if predict_fn is None:
            def predict_fn(wstate, batch):  # noqa: ARG001
                raise ValueError(
                    "this artifact was exported without a forward "
                    "program; only /generate is served")

        ispec = man.get("input_spec") or {}
        shape = [int(s) for s in (ispec.get("shape") or (1, 1))]
        return RestfulServer(
            predict_fn, wstate, shape[0], tuple(shape[1:]),
            port=port, workflow=None, engine=runner,
            input_dtype=np.dtype(ispec.get("dtype", "float32")),
            default_eos_id=man.get("eos_id"),
            vocab_size=man.get("input_vocab"))

    banner = {
        "artifact": args.artifact,
        "workflow": man.get("workflow"),
        "programs": {
            "decode": "decode" in man.get("programs", {}),
            "forward": "forward" in man.get("programs", {}),
            "prefill_buckets": man.get("buckets", [])},
    }
    if _fleet_n(args):
        # N sealed-artifact replicas behind the router: each boots the
        # whole deserialized program inventory itself, and the rolling
        # drain's restart handle reboots a replica from the SAME
        # sealed artifact (docs/serving.md "Fleet serving")
        from .runtime.deploy import DeployController

        def factory():
            srv = build_server(0)
            DeployController(server=srv,
                             drain_timeout_s=args.drain_timeout,
                             boot_source=str(args.artifact))
            return srv.start()

        return _serve_fleet(args, factory, banner)
    srv = build_server(args.serve)
    return _run_serve_loop(args, srv, banner,
                           boot_source=str(args.artifact))


def _experiment_main(argv) -> int:
    """``python -m veles_tpu experiment <action>``: inspect or cancel
    experiments in a durable store (docs/experiments.md).  ``list`` and
    ``status`` read the store directly (no running manager needed —
    trial files ARE the progress record); ``cancel`` and ``submit``
    need a live manager and go through its REST surface (``--server``),
    because only the owning process can drive or stop trials."""
    p = argparse.ArgumentParser(prog="veles_tpu experiment")
    sub = p.add_subparsers(dest="action", required=True)
    for act in ("list", "status"):
        sp = sub.add_parser(act)
        sp.add_argument("store_dir")
        if act == "status":
            sp.add_argument("id")
    sp = sub.add_parser("submit")
    sp.add_argument("--server", "-s", required=True,
                    help="fleet/replica base URL serving /experiments")
    sp.add_argument("spec", help="experiment spec JSON file or inline "
                                 "JSON object")
    sp = sub.add_parser("cancel")
    sp.add_argument("--server", "-s", required=True)
    sp.add_argument("id")
    a = p.parse_args(argv)

    from .experiments import ExperimentStore
    if a.action == "list":
        store = ExperimentStore(a.store_dir)
        print(json.dumps({"experiments": store.load_all()}, indent=1))
        return 0
    if a.action == "status":
        store = ExperimentStore(a.store_dir)
        man = store.read_manifest(a.id)
        if man is None:
            print(json.dumps({"error": f"no such experiment: {a.id}"}))
            return 1
        trials = store.load_trials(a.id)
        man["trials"] = [trials[k] for k in sorted(trials)]
        print(json.dumps(man, indent=1))
        return 0
    import urllib.request
    base = a.server.rstrip("/")
    if a.action == "submit":
        import os
        if os.path.exists(a.spec):
            with open(a.spec) as f:
                spec = json.load(f)
        else:
            spec = json.loads(a.spec)
        req = urllib.request.Request(
            f"{base}/experiments", method="POST",
            data=json.dumps(spec).encode(),
            headers={"Content-Type": "application/json"})
    else:
        req = urllib.request.Request(
            f"{base}/experiments/{a.id}", method="DELETE")
    try:
        with urllib.request.urlopen(req) as resp:
            print(json.dumps(json.load(resp), indent=1))
        return 0
    except urllib.error.HTTPError as e:
        print(e.read().decode())
        return 1


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "benchmark":
        # reference: DeviceBenchmark / device-info DB
        # (veles/accelerated_units.py:706-824, veles/backends.py:672-731)
        from .runtime.benchmark import benchmark_device
        info = benchmark_device(refresh="--refresh" in argv)
        print(json.dumps(info, indent=1))
        return 0
    if argv and argv[0] == "compare-snapshots":
        # reference: veles/scripts/compare_snapshots.py (relative diffs
        # between two Snapshotter pickles, prettytable output)
        p = argparse.ArgumentParser(
            prog="veles_tpu compare-snapshots",
            description="Per-tensor diff of two snapshot manifests "
                        "(paths, _current/_best links, or sqlite:// / "
                        "http:// snapshot URIs)")
        p.add_argument("a")
        p.add_argument("b")
        p.add_argument("--sort", choices=("name", "maxdiff", "reldiff"),
                       default="reldiff", help="row order (default: by "
                       "max relative difference, largest first)")
        p.add_argument("--top", type=int, default=0,
                       help="print only the N most-different tensors")
        p.add_argument("--json", action="store_true",
                       help="machine-readable report instead of a table")
        ca = p.parse_args(argv[1:])
        from .runtime.snapshotter import compare_snapshots
        rep = compare_snapshots(ca.a, ca.b)
        if ca.json:
            print(json.dumps(rep, indent=1))
            return 0
        rows = rep["rows"]
        if ca.sort == "maxdiff":
            rows.sort(key=lambda r: -r.get("max_abs", float("inf")))
        elif ca.sort == "reldiff":
            rows.sort(key=lambda r: -r.get("max_rel", float("inf")))
        if ca.top:
            rows = rows[:ca.top]
        print(f"{'tensor':44s} {'shape':>16s} {'max|d|':>11s} "
              f"{'mean|d|':>11s} {'max rel':>11s}")
        for r in rows:
            if r["mismatch"]:
                print(f"{r['key']:44s} MISMATCH "
                      f"{r['shape_a']}/{r['dtype_a']} vs "
                      f"{r['shape_b']}/{r['dtype_b']}")
            else:
                print(f"{r['key']:44s} {str(tuple(r['shape'])):>16s} "
                      f"{r['max_abs']:11.4g} {r['mean_abs']:11.4g} "
                      f"{r['max_rel']:11.4g}")
        for side, keys in (("a", rep["only_a"]), ("b", rep["only_b"])):
            for k in keys:
                print(f"{k:44s} ONLY IN {side}")
        for k, (va, vb) in sorted(rep["meta"].items()):
            sa, sb = repr(va), repr(vb)
            if len(sa) + len(sb) > 160:  # decision history etc.
                sa, sb = sa[:76] + "…", sb[:76] + "…"
            print(f"meta {k}: {sa} -> {sb}")
        n_diff = sum(1 for r in rep["rows"]  # count BEFORE --top cut
                     if r["mismatch"] or r.get("max_abs", 0) > 0)
        print(f"-- {len(rep['rows'])} shared tensors, {n_diff} differ; "
              f"{len(rep['only_a'])}+{len(rep['only_b'])} unmatched")
        return 0
    if argv and argv[0] == "forge":
        setup_logging()
        return _forge_main(argv[1:])
    if argv and argv[0] == "experiment":
        setup_logging()
        return _experiment_main(argv[1:])
    if "--frontend" in argv:
        # reference: veles --frontend web form -> composed cmdline
        # (veles/__main__.py:258-332)
        setup_logging()
        from .frontend import Frontend
        fe = Frontend(build_parser())
        composed = fe.wait()
        fe.close()
        if composed is None:
            return 1
        return main(composed)
    args = build_parser().parse_args(argv)
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import os
    if args.background and "VELES_DAEMONIZED" not in os.environ:
        # Classic double-fork daemonization (reference: veles --background,
        # veles/external/daemon). Must happen BEFORE any XLA client exists:
        # forking a process with live device handles corrupts them.
        pid = _daemonize(args.background_log)
        if pid > 0:  # launcher process: report the daemon pid and leave
            print(json.dumps({"daemon_pid": pid}))
            return 0
        if pid < 0:  # intermediate child died before reporting
            print("daemonization failed", file=sys.stderr)
            return 1
    setup_logging(level=10 if args.verbose else 20)

    if args.hosts and "VELES_PROCESS_ID" not in os.environ:
        # Launcher role: respawn this exact command on every host with
        # rank env vars (children skip this branch — they carry
        # VELES_PROCESS_ID). Reference: Launcher SSH slave spawn,
        # veles/launcher.py:808-842.
        from .parallel.launcher import launch_hosts
        return launch_hosts(args.hosts.split(","), argv)
    # Joins the multi-host process group when VELES_* are set (no-op
    # standalone).
    from .parallel.distributed import initialize_distributed
    initialize_distributed()

    if args.list_units:
        from .units.base import UnitRegistry
        for name in UnitRegistry.names():
            print(name)
        return 0

    if args.ensemble_test and not args.config:
        raise SystemExit("--ensemble-test needs the workflow config the "
                         "members were trained with")
    if args.compiled and not args.export:
        raise SystemExit("--compiled modifies --export DIR (it writes "
                         "the compiled artifact there)")
    if args.fleet is not None and args.serve is None:
        raise SystemExit("--fleet fronts HTTP serving with the fleet "
                         "router and needs --serve PORT")
    if args.fleet is not None and args.watch:
        # fail at parse time — _serve_fleet re-checks (it is also a
        # library entry), but a pure argv conflict must not wait for
        # a training run to finish before it fires
        raise SystemExit(
            "--watch is per-replica and conflicts with --fleet: "
            "fleet-wide version changes go through the router's "
            "coordinated swap (POST /admin/reload on the router)")
    if args.fleet is not None and args.join:
        raise SystemExit("--fleet runs the router; --join makes this "
                         "process a replica of ANOTHER router — "
                         "pick one")
    if args.join and args.serve is None:
        raise SystemExit("--join registers a serving replica with a "
                         "fleet router and needs --serve PORT")
    if args.join and args.watch:
        raise SystemExit("--watch is a per-replica auto-swap and would "
                         "silently break the fleet's all-or-nothing "
                         "version invariant on a --join'ed replica; "
                         "fleet-wide version changes go through the "
                         "router's coordinated swap (POST /admin/reload "
                         "on the router)")

    if args.artifact is not None:
        # compiled-artifact serving: no config, no model Python — the
        # sealed program set + weights blob are the whole input
        if args.serve is None:
            raise SystemExit("--artifact serves a compiled artifact "
                             "and needs --serve PORT")
        if args.config:
            raise SystemExit("--artifact serves sealed programs; a "
                             "workflow config cannot apply (drop "
                             f"{args.config!r}, or serve the config "
                             "via --serve without --artifact)")
        if args.export:
            raise SystemExit("--export needs the model config to "
                             "package; it cannot combine with "
                             "--artifact serving (export first, then "
                             "serve the artifact)")
        if args.snapshot:
            raise SystemExit("--artifact serves the artifact's sealed "
                             "weights; --snapshot cannot apply (swap "
                             "weights at runtime via POST "
                             "/admin/reload)")
        if args.generate is not None:
            raise SystemExit("--generate is a one-shot decode of a "
                             "config/snapshot model; with an artifact, "
                             "serve it and POST /generate")
        apply_overrides(root, args.overrides)
        return _serve_artifact(args)

    if not args.config:
        build_parser().print_help()
        return 2

    if args.publish:
        _publish_fmts(args.publish.partition(":")[2])  # fail fast on typos
        if (args.optimize or args.ensemble_train or args.ensemble_test
                or args.dry_run or args.curriculum):
            raise SystemExit("--publish applies to standalone training "
                             "runs (meta-workflow reports: use the "
                             "Publisher API)")
    if args.curriculum and (args.dry_run or args.export
                            or args.generate is not None
                            or args.serve is not None):
        raise SystemExit("--curriculum is a training meta-mode; "
                         "--dry-run/--export/--generate/--serve apply "
                         "to single runs (run them on the final best "
                         "snapshot)")

    if args.random_seed is not None:
        root.common.random_seed = _parse_seed(args.random_seed)
        prng.streams.reset()

    # -- curriculum mode (chained CLI phases; productized
    # configs/induction_lm64_curriculum.sh — BASELINE.md stretch bar).
    # Dispatched BEFORE _load_config: the parent only needs the config
    # PATH — each phase subprocess loads/executes it itself, so loading
    # here would double any import-time side effects. Warm start comes
    # from an explicit --snapshot (a config-manifest snapshot is a
    # single-run convenience and is not consulted).
    if args.curriculum:
        from .runtime.curriculum import CurriculumRunner
        with open(args.curriculum) as f:
            spec = json.load(f)
        extra = list(args.overrides)
        if args.platform:
            # phases run in subprocesses; the flag (not the env) selects
            # the platform there, so forward it
            extra += ["--platform", args.platform]
        seed = (_parse_seed(args.random_seed)
                if args.random_seed is not None else None)
        summary = CurriculumRunner(args.config, spec,
                                   args.curriculum_out,
                                   extra_argv=extra,
                                   initial_snapshot=args.snapshot,
                                   default_seed=seed).run()
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "phases"}))
        if args.result_file:
            with open(args.result_file, "w") as f:
                json.dump(summary, f, indent=1)
        return 0

    create, manifest_snapshot = _load_config(args.config, args.overrides)
    if manifest_snapshot and not args.snapshot:
        args.snapshot = manifest_snapshot
    if not args.platform:
        # the config-file form of --platform ("" = let JAX pick): the
        # backend has not initialized yet at this point — nothing above
        # touches a device — so the pin still lands before first use
        cfg_platform = str(root.common.get("platform", "") or "")
        if cfg_platform:
            import jax
            jax.config.update("jax_platforms", cfg_platform)
    if args.compile_cache:
        # flag wins over config/overrides; Trainer.initialize() activates
        # it right before the first compile
        root.common.compile_cache = args.compile_cache

    if args.dump_config:
        print(root.dump())
        return 0

    def trainer_factory(cfg: Config) -> Trainer:
        if create is not None:
            return create(cfg)
        return _make_trainer_from_root(cfg, args)

    # -- GA mode (reference --optimize, veles/__main__.py:716-734) ---------
    if args.optimize:
        from .genetics import GeneticOptimizer, SubprocessEvaluator
        n, _, g = args.optimize.partition(":")

        fitness, evaluator = None, None
        if args.workers > 1:
            # Reference farm-out: every chromosome is a standalone run on
            # the worker pool (veles/genetics/optimization_workflow.py).
            extra = list(args.overrides)
            if args.max_epochs:
                extra += ["--max-epochs", str(args.max_epochs)]
            if args.random_seed is not None:
                extra += ["--random-seed", str(args.random_seed)]
            evaluator = SubprocessEvaluator(
                extra, base_config=args.config, n_workers=args.workers)
        else:
            def fitness(cfg: Config) -> float:
                t = trainer_factory(cfg)
                t.initialize()
                t.run()
                return t.decision.best_value

        ga = GeneticOptimizer(root, fitness, population_size=int(n),
                              generations=int(g) if g else 10,
                              evaluator=evaluator)
        best = ga.run()
        out = {"best_fitness": best.fitness, "best_genome": best.genome}
        print(json.dumps(out))
        if args.result_file:
            import jax
            if jax.process_index() == 0:  # one writer per gang
                with open(args.result_file, "w") as f:
                    json.dump({**out, "history": ga.history}, f, indent=1)
        return 0

    # -- ensemble train (reference --ensemble-train N:r) -------------------
    if args.ensemble_train:
        from .ensemble import EnsembleTrainer
        n, _, r = args.ensemble_train.partition(":")

        member_factory, cli_argv = None, None
        if args.workers > 1:
            # Reference farm-out: each member is a standalone CLI run
            # (veles/ensemble/base_workflow.py:135-143).
            cli_argv = [args.config, *args.overrides]
            if args.max_epochs:
                cli_argv += ["--max-epochs", str(args.max_epochs)]
        else:
            def member_factory(member_id, seed, train_ratio):
                root.common.random_seed = seed
                prng.streams.reset()
                # Standard-path loaders accept bagging args via the Loader
                # base; create()-style configs must honor root.loader
                # themselves.
                root.loader.train_ratio = train_ratio
                root.loader.subset_seed = seed
                return trainer_factory(root)

        et = EnsembleTrainer(member_factory, int(n),
                             float(r) if r else 0.8,
                             out_dir=args.snapshot_dir or "ensemble",
                             n_workers=args.workers, cli_argv=cli_argv)
        results = et.run()
        print(json.dumps({"members": len(results)}))
        return 0

    # -- ensemble test (reference --ensemble-test: weighted vote over the
    # stored member snapshots, veles/ensemble/test_workflow.py:50-107) ----
    if args.ensemble_test:
        from .ensemble import EnsembleTester
        from .loader.base import VALID

        from .units.base import spec_of

        trainer = trainer_factory(root)
        trainer.loader.initialize()
        if trainer.loader.class_lengths[VALID] == 0:
            raise SystemExit(
                "--ensemble-test needs a validation split in the loader")
        batch = next(trainer.loader.iter_epoch(VALID))
        trainer.workflow.build({k: spec_of(v) for k, v in batch.items()})
        tester = EnsembleTester(lambda: trainer.workflow,
                                args.ensemble_test)
        err = tester.error_rate(trainer.loader.iter_epoch(VALID))
        out = {"ensemble_members": len(tester.members),
               "valid_error_pct": err}
        print(json.dumps(out))
        if args.result_file:
            with open(args.result_file, "w") as f:
                json.dump(out, f, indent=1)
        return 0

    # -- standalone training ------------------------------------------------
    trainer = trainer_factory(root)
    status_server = None
    if args.status_port is not None or args.plots:
        # Live observability: recorder autosaves metric-curve PNGs each
        # epoch; the status server embeds them in an auto-refreshing
        # page — a running job is watchable at an HTTP URL (reference:
        # web_status.py + the WebAgg graphics backend).
        from .plotting import MetricsRecorder
        from .runtime.status import StatusReporter, StatusServer
        plots_dir = args.plots or "plots"
        os.makedirs(plots_dir, exist_ok=True)
        if trainer.recorder is None:
            trainer.recorder = MetricsRecorder(
                name=trainer.workflow.name, out_dir=plots_dir,
                autosave_png=True)
        else:
            # a create()-style config may have wired its own recorder;
            # the flags still promise live plots — upgrade it in place
            trainer.recorder.out_dir = trainer.recorder.out_dir \
                or plots_dir
            trainer.recorder.autosave_png = True
        if args.status_port is not None:
            if trainer.status is None:
                trainer.status = StatusReporter(
                    os.path.join(plots_dir, "status.json"),
                    name=trainer.workflow.name, plots_dir=plots_dir)
            elif trainer.status.plots_dir is None:
                trainer.status.plots_dir = trainer.recorder.out_dir
            if trainer.status.graph_svg is None:
                # the page embeds the live workflow graph (reference:
                # web/viz.js rendered the DOT feed in the browser)
                svg_path = os.path.join(plots_dir, "workflow.svg")
                try:
                    with open(svg_path, "w") as f:
                        f.write(trainer.workflow.generate_svg())
                    trainer.status.graph_svg = svg_path
                except OSError:
                    pass
            status_server = StatusServer(
                trainer.status, port=args.status_port).start()
    if args.snapshot_dir and trainer.snapshotter is None:
        # create()-style configs get the CLI snapshot dir too (the standard
        # path wires this inside _make_trainer_from_root)
        trainer.snapshotter = Snapshotter(trainer.workflow.name,
                                          args.snapshot_dir)
    if args.dry_run == "init":
        trainer.loader.initialize()
        print(json.dumps({"dry_run": "init",
                          "class_lengths": trainer.loader.class_lengths}))
        return 0
    trainer.initialize()
    if args.visualize:
        _write_graph(trainer.workflow, args.visualize)
    if args.dry_run == "build":
        print(json.dumps({"dry_run": "build",
                          "checksum": trainer.workflow.checksum(),
                          "n_params": trainer.workflow.n_params(
                              trainer.wstate)}))
        return 0
    if args.snapshot:
        trainer.restore(args.snapshot)
    if args.export:
        spec = trainer._batch_spec["@input"]
        input_spec = {"shape": list(spec.shape),
                      "dtype": str(spec.dtype)}
        if args.compiled:
            # sealed compiled artifact: StableHLO programs + manifest +
            # weights (export/compiled.py); served via --serve
            # --artifact with zero model Python
            if args.export.endswith(".zip"):
                raise SystemExit("--compiled exports a DIRECTORY "
                                 "artifact (programs + manifest + "
                                 "weights), not a .zip")
            from .export import export_compiled, manifest_summary
            man = export_compiled(
                trainer.workflow, trainer.wstate, args.export,
                input_spec=input_spec, eos_id=args.eos_id)
            out = {"exported": args.export, "compiled": True,
                   "manifest": manifest_summary(man)}
        else:
            from .export import export_package
            export_package(trainer.workflow, trainer.wstate, args.export,
                           input_spec=input_spec)
            out = {"exported": args.export,
                   "units": len(trainer.workflow.units)}
        print(json.dumps(out))
        if args.result_file:
            with open(args.result_file, "w") as f:
                json.dump(out, f, indent=1)
        return 0
    if args.serve is not None:
        # HTTP serving mode: the reference's RESTfulAPI unit as a CLI
        # switch (veles/restful_api.py:78) — POST /predict on the chain
        # head, POST /generate for sequence chains, wrapped in the model
        # lifecycle control plane (runtime/deploy.py): GET /healthz +
        # /ready + /models, POST /admin/reload hot swaps, graceful
        # drain on SIGTERM / POST /admin/drain
        from .runtime.restful import RestfulServer
        wf = trainer.workflow
        head = wf.default_output()
        spec = trainer._batch_spec["@input"]
        if _fleet_n(args):
            # N live replica stacks behind the router — each replica
            # gets its OWN DecodeEngine (own slots/queue/scheduler)
            # over the shared read-only weights, so fleet dispatch has
            # real per-replica load to balance
            from .logger import Logger as _Logger
            from .runtime.deploy import DeployController
            from .runtime.engine import DecodeEngine
            from .units.workflow import WorkflowError

            def factory():
                engine = None
                try:
                    engine = DecodeEngine(wf, dict(trainer.wstate),
                                          status=trainer.status)
                except WorkflowError as e:  # a chain with no decode
                    # path still serves /predict per replica; an engine
                    # that fails to COMPILE is an error, not a downgrade
                    _Logger().warning(
                        "fleet replica serves forward-only (no decode "
                        "engine: %s)", e)
                srv = RestfulServer(
                    wf.make_predict_step(head), dict(trainer.wstate),
                    int(spec.shape[0]), tuple(spec.shape[1:]),
                    port=0, workflow=wf, engine=engine,
                    input_dtype=spec.dtype)
                DeployController(server=srv,
                                 drain_timeout_s=args.drain_timeout,
                                 status=trainer.status,
                                 boot_source=args.snapshot or "live")
                return srv.start()

            return _serve_fleet(args, factory, {"predict_head": head})
        srv = RestfulServer(
            wf.make_predict_step(head), trainer.wstate,
            int(spec.shape[0]), tuple(spec.shape[1:]),
            port=args.serve, workflow=wf,
            input_dtype=spec.dtype)
        return _run_serve_loop(args, srv, {"predict_head": head},
                               status=trainer.status,
                               boot_source=args.snapshot or "live")
    if args.generate is not None:
        # decode mode: the trained (or restored) sequence model emits a
        # continuation instead of training (reference has no LM family;
        # this pairs with `veles_serve --generate` for the native path)
        import numpy as np

        from .runtime.generate import generate as _generate
        if not args.prompt:
            raise SystemExit("--generate needs --prompt "
                             "(token ids, or @file.npy)")
        if (args.top_k is not None or args.top_p is not None) \
                and args.temperature <= 0:
            raise SystemExit(
                "--top-k/--top-p filter SAMPLING and need "
                "--temperature > 0 (temperature 0 is greedy decoding, "
                "which would silently ignore them)")
        if args.top_k is not None and args.top_k < 1:
            raise SystemExit(f"--top-k must be >= 1, got {args.top_k}")
        if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
            raise SystemExit(f"--top-p must be in (0, 1], got "
                             f"{args.top_p}")
        if args.beams < 1:
            raise SystemExit(f"--beams must be >= 1, got {args.beams} "
                             "(a value < 1 would silently fall back to "
                             "greedy/sampling decode)")
        if args.beams <= 1 and args.length_penalty:
            raise SystemExit(
                "--length-penalty shapes BEAM scores and needs "
                "--beams > 1 (greedy/sampling decode would silently "
                "ignore it)")
        if args.prompt.startswith("@"):
            prompt = np.atleast_2d(
                np.load(args.prompt[1:])).astype(np.int32)
        else:
            rows = [[int(t) for t in row.split(",") if t.strip()]
                    for row in args.prompt.split(";") if row.strip()]
            if not rows or len({len(r) for r in rows}) != 1:
                raise SystemExit(
                    "--prompt rows must be non-empty and equal length "
                    f"(got lengths {[len(r) for r in rows]})")
            prompt = np.asarray(rows, np.int32)
        import jax as _jax
        key = _jax.random.key(int(root.common.get("random_seed", 0)))
        if args.beams > 1:
            if args.temperature > 0:
                raise SystemExit(
                    "--beams is deterministic search; drop "
                    "--temperature/--top-k/--top-p or use beams=1")
            from .runtime.generate import generate_beam as _gen_beam
            toks, scores = _gen_beam(
                trainer.workflow, trainer.wstate, prompt, args.generate,
                beams=args.beams, eos_id=args.eos_id,
                length_penalty=args.length_penalty)
            out = {"prompt_len": int(prompt.shape[1]),
                   "tokens": np.asarray(toks).tolist(),
                   "scores": np.asarray(scores).tolist()}
            print(json.dumps(out))
            if args.result_file:
                with open(args.result_file, "w") as f:
                    json.dump(out, f, indent=1)
            return 0
        toks = _generate(trainer.workflow, trainer.wstate, prompt,
                         args.generate, temperature=args.temperature,
                         top_k=args.top_k, top_p=args.top_p,
                         eos_id=args.eos_id, key=key)
        out = {"prompt_len": int(prompt.shape[1]),
               "tokens": np.asarray(toks).tolist()}
        print(json.dumps(out))
        if args.result_file:
            with open(args.result_file, "w") as f:
                json.dump(out, f, indent=1)
        return 0
    if args.profile_units:
        from .loader.base import TRAIN, VALID as _VALID
        klass = TRAIN if trainer.loader.class_lengths[TRAIN] else _VALID
        batch = next(trainer.loader.iter_epoch(klass))
        rows = trainer.workflow.profile_units(trainer.wstate, batch)
        print(trainer.workflow.format_profile(rows))
    import contextlib
    profile_cm = contextlib.nullcontext()
    if args.profile:
        import jax
        profile_cm = jax.profiler.trace(args.profile)
    try:
        with profile_cm:
            results = trainer.run()
    finally:
        if status_server is not None:
            status_server.stop()
        _maybe_write_trace(args)
    print(json.dumps(results))
    if args.publish:
        # after the results are emitted — a report typo must never eat a
        # finished training run
        from .publishing import Publisher
        out_dir, _, fmts = args.publish.partition(":")
        kinds = _publish_backends()
        backends = [kinds[f](out_dir) for f in _publish_fmts(fmts)]
        pub = Publisher(trainer.workflow.name, backends=backends)
        pub.gather(trainer=trainer, config=root)
        pub.publish()
    if args.result_file:
        import jax
        if jax.process_index() == 0:  # one writer per gang (cf. master's
            with open(args.result_file, "w") as f:  # --result-file)
                json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
