"""Recipe launcher: declarative serving topologies → running processes.

Fills the role of the reference's deployment recipes + K8s operator
surface (reference: recipes/*/deploy.yaml `DynamoGraphDeployment` CRDs,
deploy/cloud/operator) in a TPU-native shape: a `TpuServeDeployment`
YAML names the model, the frontend(s), and worker pools with their mesh
geometry (tp/pp/dp/ep/sp, multi-host node counts) — everything the
operator would template into pods maps 1:1 onto this framework's
component CLIs (`dynamo_tpu.components.*`).

Two consumers:

- ``plan``: print the exact process commands a deployment implies (what
  a K8s operator would put in pod specs — also the contract tests pin).
- ``up``: run the whole topology locally (one host): coordinator →
  kv-store → workers → frontends, readiness-gated, torn down on SIGINT.
  `--engine mocker` overrides every worker's engine for chip-free runs.

    python -m dynamo_tpu.launch.recipe plan recipes/llama-3-70b/disagg-v5e-64.yaml
    python -m dynamo_tpu.launch.recipe up recipes/llama-3-8b/agg.yaml --engine mocker
"""

from __future__ import annotations

import argparse
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import yaml

from dynamo_tpu.utils.logging import configure_logging, get_logger

log = get_logger("recipe")

KIND = "TpuServeDeployment"


@dataclass
class Process:
    """One planned process: a component module + argv."""

    name: str
    module: str
    args: list[str]
    replicas: int = 1
    ready_line: str | None = None
    # Processes sharing a group are spawned TOGETHER before any readiness
    # wait — multi-host ranks block in jax.distributed.initialize until
    # every rank exists, so gating rank 0 alone would deadlock.
    group: str | None = None

    def argv(self) -> list[str]:
        return [sys.executable, "-m", self.module, *self.args]


@dataclass
class Plan:
    name: str
    coordinator_url: str
    processes: list[Process] = field(default_factory=list)


def _engine_args(engine: dict[str, Any]) -> list[str]:
    flags = {
        "blockSize": "--block-size", "numBlocks": "--num-blocks",
        "maxBatchSize": "--max-batch-size", "maxModelLen": "--max-model-len",
        "hostKvBlocks": "--host-kv-blocks", "diskKvPath": "--disk-kv-path",
        "remoteKvAddr": "--remote-kv-addr",
    }
    # Boolean switches: present-and-truthy emits the bare flag.
    switches = {"globalPrefixCache": "--global-prefix-cache"}
    out: list[str] = []
    for key, flag in flags.items():
        if key in engine:
            out += [flag, str(engine[key])]
    for key, flag in switches.items():
        if engine.get(key):
            out.append(flag)
    return out


def _mesh_args(mesh: dict[str, Any]) -> list[str]:
    out: list[str] = []
    for axis in ("tp", "pp", "dp", "ep", "sp"):
        if axis in mesh:
            out += [f"--{axis}", str(mesh[axis])]
    return out


def load_spec(path: str | Path) -> dict:
    with open(path) as f:
        doc = yaml.safe_load(f)
    if not isinstance(doc, dict) or doc.get("kind") != KIND:
        raise ValueError(f"{path}: expected kind {KIND}")
    if "spec" not in doc or "metadata" not in doc:
        raise ValueError(f"{path}: missing spec/metadata")
    return doc


def build_plan(doc: dict, engine_override: str | None = None,
               coordinator_port: int = 4222) -> Plan:
    """Pure mapping: deployment spec → process list (the operator's job)."""
    spec = doc["spec"]
    name = doc["metadata"]["name"]
    coord = spec.get("coordinator", {})
    url = coord.get("external") or f"tcp://127.0.0.1:{coord.get('port', coordinator_port)}"
    plan = Plan(name=name, coordinator_url=url)

    if not coord.get("external"):
        plan.processes.append(Process(
            name="coordinator", module="dynamo_tpu.transports.coordinator",
            args=["--host", "0.0.0.0", "--port", str(coord.get("port", coordinator_port))],
            ready_line="COORDINATOR_READY"))

    if "kvStore" in spec:
        ks = spec["kvStore"]
        plan.processes.append(Process(
            name="kv-store", module="dynamo_tpu.components.kv_store",
            args=["--coordinator", url,
                  "--capacity-gib", str(ks.get("capacityGib", 4)),
                  "--port", str(ks.get("port", 0))],
            ready_line="KV_STORE_READY"))

    if "encoder" in spec:
        enc = spec["encoder"] or {}
        plan.processes.append(Process(
            name="encoder", module="dynamo_tpu.components.encode",
            args=["--coordinator", url,
                  "--image-tokens", str(enc.get("imageTokens", 8)),
                  "--lm-hidden", str(enc.get("lmHidden", 64)),
                  "--image-size", str(enc.get("imageSize", 64))],
            replicas=int(enc.get("replicas", 1)),
            ready_line="ENCODE_READY"))

    model = spec["model"]
    for w in spec.get("workers", []):
        args = ["--coordinator", url, "--model", model,
                "--engine", engine_override or w.get("engine_kind", "jax")]
        if w.get("servedModelName") or spec.get("servedModelName"):
            args += ["--served-model-name",
                     w.get("servedModelName") or spec["servedModelName"]]
        parsers = w.get("parsers") or spec.get("parsers") or {}
        if parsers.get("toolCall"):
            args += ["--tool-call-parser", parsers["toolCall"]]
        if parsers.get("reasoning"):
            args += ["--reasoning-parser", parsers["reasoning"]]
        role = w.get("role", "none")
        if role in ("prefill", "decode"):
            args += ["--disagg", role]
            if role == "prefill":
                args += ["--component", "prefill"]
        args += _mesh_args(w.get("mesh", {}))
        args += _engine_args(w.get("engine", {}))
        nodes = int(w.get("nodes", 1))
        if engine_override and engine_override != "jax":
            # Chip-free override (mocker): a simulator doesn't shard — one
            # process stands in for the whole multi-host engine.
            nodes = 1
        if nodes > 1:
            # Multi-host: one process per (replica, rank); rank 0 leads
            # (parallel/multihost.py resolves the leader through the
            # coordination service). Each replica rendezvouses in its own
            # group — two replicas of one component must not share a
            # leader key.
            for rep in range(int(w.get("replicas", 1))):
                group = f"{name}.{w['name']}.r{rep}"
                for rank in range(nodes):
                    plan.processes.append(Process(
                        name=f"{w['name']}-r{rep}-rank{rank}",
                        module="dynamo_tpu.components.worker",
                        args=args + ["--num-nodes", str(nodes),
                                     "--node-rank", str(rank),
                                     "--multihost-group", group],
                        replicas=1, group=group,
                        ready_line="WORKER_READY" if rank == 0 else None))
        else:
            plan.processes.append(Process(
                name=w["name"], module="dynamo_tpu.components.worker",
                args=args, replicas=int(w.get("replicas", 1)),
                ready_line="WORKER_READY"))

    fe = spec.get("frontend", {})
    fe_args = ["--coordinator", url,
               "--port", str(fe.get("port", 8080)),
               "--router-mode", fe.get("routerMode", "kv")]
    if "encoder" in spec:
        fe_args += ["--encoder-endpoint", "dyn://dynamo.encoder.encode"]
    if "grpcPort" in fe:
        fe_args += ["--grpc-port", str(fe["grpcPort"])]
    if "migrationLimit" in fe:
        fe_args += ["--migration-limit", str(fe["migrationLimit"])]
    qos = fe.get("qos", {})
    if qos.get("enabled") is False:
        fe_args += ["--no-qos"]
    for key, flag in (("defaultPriority", "--qos-default-priority"),
                      ("rateLimitRps", "--qos-rate-limit-rps"),
                      ("rateBurst", "--qos-rate-burst"),
                      ("degradeQueueDepth", "--qos-degrade-queue-depth"),
                      ("shedQueueDepth", "--qos-shed-queue-depth"),
                      ("maxQueueDepth", "--qos-max-queue-depth"),
                      ("clampMaxTokens", "--qos-clamp-max-tokens"),
                      ("defaultDeadlineMs", "--qos-default-deadline-ms")):
        if key in qos:
            fe_args += [flag, str(qos[key])]
    plan.processes.append(Process(
        name="frontend", module="dynamo_tpu.components.frontend",
        args=fe_args, replicas=int(fe.get("replicas", 1)),
        ready_line="FRONTEND_READY"))

    agg_port = None
    if spec.get("aggregator", {}).get("enabled"):
        ag = spec["aggregator"]
        agg_port = int(ag.get("port", 9090))
        ag_args = ["--coordinator", url, "--port", str(agg_port)]
        for key, flag in (("scrapeInterval", "--scrape-interval"),
                          ("scrapeTimeout", "--scrape-timeout"),
                          ("stalenessTtl", "--staleness-ttl"),
                          ("sloSpec", "--slo-spec")):
            if key in ag:
                ag_args += [flag, str(ag[key])]
        plan.processes.append(Process(
            name="aggregator", module="dynamo_tpu.components.aggregator",
            args=ag_args, ready_line="AGGREGATOR_READY"))

    if spec.get("planner", {}).get("enabled"):
        pl = spec["planner"]
        pl_args = ["--coordinator", url]
        if agg_port is not None:
            # Close the SLA loop: the planner consumes the aggregator's
            # fleet-wide rollup instead of a single frontend.
            pl_args += ["--fleet-url", f"http://127.0.0.1:{agg_port}"]
        for key, flag in (("ttftSla", "--ttft-sla"), ("itlSla", "--itl-sla"),
                          ("minReplicas", "--min-replicas"),
                          ("maxReplicas", "--max-replicas"),
                          ("chipBudget", "--chip-budget"),
                          ("adjustmentInterval", "--adjustment-interval"),
                          ("mode", "--mode")):
            if key in pl:
                pl_args += [flag, str(pl[key])]
        plan.processes.append(Process(
            name="planner", module="dynamo_tpu.components.planner",
            args=pl_args))
    return plan


def format_plan(plan: Plan) -> str:
    lines = [f"deployment {plan.name} (coordinator {plan.coordinator_url}):"]
    for p in plan.processes:
        rep = f" x{p.replicas}" if p.replicas > 1 else ""
        lines.append(f"  [{p.name}{rep}] " + " ".join(p.argv()))
    return "\n".join(lines)


class _Child:
    """A spawned process with a drain thread: the pipe is read for the
    process's whole life (a full 64KB pipe would block the child mid-serve)
    and the ready line is detected without blocking the launcher."""

    def __init__(self, spec: Process, idx: int):
        import threading

        self.spec = spec
        self.proc = subprocess.Popen(
            spec.argv(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self.name = spec.name if spec.replicas == 1 else f"{spec.name}[{idx}]"
        self.ready = threading.Event()
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:  # type: ignore[union-attr]
            sys.stdout.write(f"{self.name}: {line}")
            sys.stdout.flush()
            if self.spec.ready_line and self.spec.ready_line in line:
                self.ready.set()

    def wait_ready(self, deadline: float) -> None:
        while not self.ready.wait(timeout=0.25):
            if self.proc.poll() is not None:
                raise RuntimeError(f"{self.name} exited rc={self.proc.returncode} "
                                   "before ready")
            if time.monotonic() > deadline:
                raise TimeoutError(f"{self.name} not ready in time")


def run_local(plan: Plan, timeout: float = 600.0) -> None:
    """Launch every process on this host. Processes are readiness-gated in
    plan order, except that a ``group`` (multi-host rank set) is spawned in
    full before its readiness wait — rank 0 cannot become ready until every
    follower has joined the jax.distributed rendezvous."""
    children: list[_Child] = []

    def stop_all() -> None:
        for c in reversed(children):
            if c.proc.poll() is None:
                c.proc.terminate()
        for c in reversed(children):
            try:
                c.proc.wait(10)
            except subprocess.TimeoutExpired:
                c.proc.kill()

    def spawn(p: Process) -> list[_Child]:
        out = []
        for r in range(p.replicas):
            c = _Child(p, r)
            children.append(c)
            out.append(c)
            log.info("started %s pid=%d", c.name, c.proc.pid)
        return out

    try:
        i = 0
        procs = plan.processes
        while i < len(procs):
            group = procs[i].group
            batch: list[_Child] = []
            if group is None:
                batch += spawn(procs[i])
                i += 1
            else:  # spawn the whole rank group before any wait
                while i < len(procs) and procs[i].group == group:
                    batch += spawn(procs[i])
                    i += 1
            deadline = time.monotonic() + timeout
            for c in batch:
                if c.spec.ready_line:
                    c.wait_ready(deadline)
        print(f"RECIPE_UP {plan.name} processes={len(children)}", flush=True)
        # Block BEFORE waiting: bare sigwait races the default SIGTERM
        # action (process death without the finally → leaked children).
        signal.pthread_sigmask(signal.SIG_BLOCK,
                               {signal.SIGINT, signal.SIGTERM})
        signal.sigwait({signal.SIGINT, signal.SIGTERM})
    finally:
        stop_all()


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser("dynamo-recipe", description=__doc__)
    ap.add_argument("cmd", choices=["plan", "up"])
    ap.add_argument("recipe")
    ap.add_argument("--engine", default=None,
                    help="override every worker's engine (e.g. mocker)")
    ap.add_argument("--start-timeout", type=float, default=600.0)
    ns = ap.parse_args(argv)
    configure_logging()
    plan = build_plan(load_spec(ns.recipe), engine_override=ns.engine)
    if ns.cmd == "plan":
        print(format_plan(plan))
        return
    run_local(plan, timeout=ns.start_timeout)


if __name__ == "__main__":
    main()
