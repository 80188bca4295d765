"""Multi-host engine bring-up: one SPMD engine spanning N processes.

Fills the role of the reference's multi-node engine configuration
(reference: lib/llm/src/engines.rs:29-44 — ``MultiNodeConfig { num_nodes,
node_rank, leader_addr }``; the sglang slurm launch pattern,
components/backends/sglang/slurm_jobs/) — the JAX way:

- Every rank calls :func:`initialize_distributed`
  (``jax.distributed.initialize``), after which ``jax.devices()`` is the
  GLOBAL device set and one :class:`~dynamo_tpu.parallel.mesh.MeshConfig`
  mesh spans all hosts. Collectives ride ICI within a slice and DCN across
  slices — inserted by XLA, never hand-written.
- Multi-controller JAX requires every process to execute the *same program
  sequence with the same shapes*. The engine's host-side state machine
  (scheduler, prefix pool, sampling seeds) is deterministic given the same
  request/abort stream, so the **leader** (rank 0) serves the endpoint and
  broadcasts every state-changing op — ``add``, ``abort``, ``step`` — over
  a framed TCP op channel *before* applying it locally. **Followers**
  replay the identical op stream, reach identical dispatch decisions, and
  execute the identical XLA programs, which lines the collectives up.
- The leader's resolved engine essentials (num_blocks above all — it may be
  auto-sized from device memory, which can differ per host) ship in the
  ``hello`` frame; followers construct their EngineCore from it, so the
  schedulers can never diverge on capacity.

Leader discovery mirrors the reference's etcd pattern: rank 0 publishes
``leader_addr`` under the coordination service; other ranks poll for it
(:func:`publish_leader_addr` / :func:`resolve_leader_addr`).

Disagg and KVBM compose with this: named core ops (engine.CORE_OPS — KV
stage/release/import) ride the same op stream, so every rank stages and
injects ITS cache shard in lockstep (disagg/sharded.py). Only the
closure-based ``run_in_core`` stays refused on a multi-host leader — a
closure can't be broadcast.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

import msgpack

from dynamo_tpu.utils.logging import get_logger

log = get_logger("multihost")

LEADER_KEY_FMT = "multinode/{group}/leader"
# The op channel listens one port above the jax coordinator by convention.
OP_PORT_OFFSET = 1


@dataclass(frozen=True)
class MultiNodeConfig:
    """Analog of the reference's MultiNodeConfig (engines.rs:29-44)."""

    num_nodes: int = 1
    node_rank: int = 0
    # host:port of the rank-0 jax distributed coordinator.
    leader_addr: str = ""
    # Op-channel port (0 = coordinator port + OP_PORT_OFFSET).
    op_port: int = 0

    @property
    def is_leader(self) -> bool:
        return self.node_rank == 0

    @property
    def enabled(self) -> bool:
        return self.num_nodes > 1

    def resolved_op_port(self) -> int:
        if self.op_port:
            return self.op_port
        return int(self.leader_addr.rsplit(":", 1)[1]) + OP_PORT_OFFSET


def vote_min(n: int) -> int:
    """Mesh-wide minimum of a per-rank count — THE all-or-nothing primitive
    that keeps nondeterministic effects (IO failures, shared-store
    hit/miss) rank-consistent on a multi-host engine: every rank truncates
    its plan to the minimum, so divergent local outcomes can never become
    divergent XLA programs. Identity on a single process. Must be called
    at the same op-stream position on every rank (it is a collective)."""
    import jax

    if jax.process_count() <= 1:
        return n
    import numpy as np
    from jax.experimental import multihost_utils

    return int(np.min(multihost_utils.process_allgather(
        np.array([n], np.int32))))


def initialize_distributed(mn: MultiNodeConfig) -> None:
    """``jax.distributed.initialize`` with the MultiNodeConfig; call ONCE
    per process, before any other jax use."""
    import jax

    jax.distributed.initialize(
        coordinator_address=mn.leader_addr,
        num_processes=mn.num_nodes,
        process_id=mn.node_rank,
    )
    log.info("jax.distributed up: rank %d/%d, %d global devices",
             mn.node_rank, mn.num_nodes, len(jax.devices()))
    # Establish the cross-process collective context NOW, while every rank
    # is still in lockstep from the init barrier. The backend's context
    # creation (Gloo on CPU) is a rendezvous with a short timeout; deferring
    # it to the engine's first real collective means uneven EngineCore
    # build/compile times can blow the window (observed: 30s GetKeyValue
    # timeout on the leader while the follower was still compiling).
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    warm_mesh = Mesh(np.array(devs), ("all",))
    x = jax.device_put(jnp.ones((len(devs),), jnp.float32),
                       NamedSharding(warm_mesh, P("all")))
    total = float(jnp.sum(x).block_until_ready())  # all-reduce across ranks
    assert total == float(len(devs)), f"collective warmup wrong: {total}"
    log.info("cross-process collective context established (%d devices)", len(devs))


# ---------------------------------------------------------------------------
# Leader discovery over the coordination service
# ---------------------------------------------------------------------------

async def publish_leader_addr(client, group: str, leader_addr: str,
                              op_port: int = 0, lease_id: int = 0) -> None:
    """Rank 0: advertise the jax coordinator address AND the (already-bound)
    op-channel port (etcd-pattern analog of the reference's leader bootstrap,
    lib/runtime/src/utils/leader_worker_barrier.rs). Publishing the real
    bound op port — instead of a port+1 convention — removes the race where
    an unrelated process grabs the conventional port between bind attempts."""
    import json

    payload = json.dumps({"leader_addr": leader_addr, "op_port": op_port})
    await client.put(LEADER_KEY_FMT.format(group=group), payload.encode(), lease_id)


async def resolve_leader_addr(client, group: str, timeout: float = 60.0) -> tuple[str, int]:
    """Ranks > 0: poll the coordination service for (leader_addr, op_port)."""
    import json

    deadline = time.monotonic() + timeout
    key = LEADER_KEY_FMT.format(group=group)
    while time.monotonic() < deadline:
        val = await client.get(key)
        if val:
            obj = json.loads(val.decode())
            return obj["leader_addr"], int(obj.get("op_port", 0))
        import asyncio

        await asyncio.sleep(0.2)
    raise TimeoutError(f"no leader address published at {key} within {timeout}s")


# ---------------------------------------------------------------------------
# Sync framed sockets (the engine-core thread is synchronous; these are the
# blocking cousins of transports/wire.py's asyncio codec, same framing)
# ---------------------------------------------------------------------------

def send_frame(sock: socket.socket, obj: Any) -> None:
    payload = msgpack.packb(obj, use_bin_type=True)
    sock.sendall(struct.pack(">I", len(payload)) + payload)


def recv_frame(sock: socket.socket) -> Any | None:
    """Read one frame; None on clean EOF."""
    header = _recv_exact(sock, 4)
    if header is None:
        return None
    (length,) = struct.unpack(">I", header)
    payload = _recv_exact(sock, length)
    if payload is None:
        return None
    return msgpack.unpackb(payload, raw=False)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


# ---------------------------------------------------------------------------
# Leader op channel
# ---------------------------------------------------------------------------

class LeaderOpChannel:
    """Rank 0's broadcast channel: accepts num_nodes-1 follower connections,
    then replicates every state-changing engine op to all of them in order.

    ``broadcast`` is called from the engine-core thread; sends are blocking
    (frames are tiny and followers read eagerly — a follower that stalls
    stalls the engine, which is the correct failure mode for SPMD: running
    ahead would hang in a collective anyway)."""

    def __init__(self, port: int, num_followers: int):
        self.num_followers = num_followers
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind(("0.0.0.0", port))  # port 0 → OS-assigned, race-free
        self.port = self._server.getsockname()[1]
        self._server.listen(num_followers)
        self._conns: list[socket.socket] = []
        self._lock = threading.Lock()

    def accept_followers(self, timeout: float = 300.0) -> None:
        self._server.settimeout(timeout)
        while len(self._conns) < self.num_followers:
            conn, addr = self._server.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conns.append(conn)
            log.info("follower %d/%d connected from %s",
                     len(self._conns), self.num_followers, addr)

    def wait_ready(self, timeout: float = 600.0) -> list[dict]:
        """Block until every follower has acked readiness (EngineCore built,
        op replay about to start). Serving before this would let the
        leader's first dispatch race far ahead of followers still building
        their engines. Returns the ready payloads (``ready_infos`` keeps
        them too) — a prefill-role follower's ack carries its shard-server
        address + (layer, head) box for disagg kv_transfer_params."""
        self.ready_infos: list[dict] = []
        for conn in self._conns:
            conn.settimeout(timeout)
            ack = recv_frame(conn)
            if ack is None or ack.get("op") != "ready":
                raise RuntimeError(f"follower sent {ack!r} instead of ready")
            conn.settimeout(None)
            self.ready_infos.append(ack)
        log.info("all %d followers ready", self.num_followers)
        return self.ready_infos

    def broadcast(self, op: dict) -> None:
        with self._lock:
            dead = []
            for conn in self._conns:
                try:
                    send_frame(conn, op)
                except OSError as exc:
                    log.error("follower send failed (%s); dropping conn", exc)
                    dead.append(conn)
            for conn in dead:
                self._conns.remove(conn)
                conn.close()
            if dead:
                # A lost follower means its devices stop participating in
                # collectives — the next dispatch would hang. Fail loudly.
                raise RuntimeError(
                    f"lost {len(dead)} follower connection(s); multi-host "
                    "engine cannot continue")

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.close()
            except OSError:
                pass
        self._server.close()


def connect_to_leader(host: str, port: int, timeout: float = 300.0) -> socket.socket:
    deadline = time.monotonic() + timeout
    last: Exception | None = None
    while time.monotonic() < deadline:
        try:
            sock = socket.create_connection((host, port), timeout=10.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.settimeout(None)
            return sock
        except OSError as exc:
            last = exc
            time.sleep(0.3)
    raise TimeoutError(f"could not reach leader op channel {host}:{port}: {last}")


# ---------------------------------------------------------------------------
# Follower loop
# ---------------------------------------------------------------------------

def follower_loop(core_factory: Callable[[dict], Any], sock: socket.socket) -> None:
    """Replay the leader's op stream against a locally-built EngineCore.

    ``core_factory(hello)`` builds the EngineCore AFTER the leader's hello
    frame arrives, from the leader's resolved engine essentials — so
    capacity-dependent scheduling (num_blocks) can never diverge. Runs until
    the leader disconnects (clean EOF) — the follower then drains its
    in-flight step and returns.
    """
    hello = recv_frame(sock)
    if hello is None or hello.get("op") != "hello":
        raise RuntimeError(f"expected hello from leader, got {hello!r}")
    core = core_factory(hello)
    ready: dict[str, Any] = {"op": "ready"}
    if hello.get("disagg_role") == "prefill":
        # This rank must serve ITS cache shard of staged transfers; the
        # address advertised is this host's IP on the route to the leader
        # (what the decode side can reach it by in the common topology).
        addr = core.start_shard_server(sock.getsockname()[0])
        ready["shard_addr"] = addr
        ready["shard_box"] = list(core.my_box())
    send_frame(sock, ready)
    from dynamo_tpu.protocols.common import PreprocessedRequest

    pending = None
    while True:
        op = recv_frame(sock)
        if op is None:
            break
        kind = op["op"]
        if kind == "add":
            # "now" pins deadline-expiry to the leader's clock so every
            # rank makes the same admit decision (engine QoS deadlines).
            core.add_request(PreprocessedRequest.from_dict(op["req"]),
                             now=op.get("now"))
        elif kind == "abort":
            core.abort(op["rid"])
        elif kind == "reap":
            core.reap_expired(op.get("now"))
        elif kind == "exec":
            # Replayed named core op (disagg KV stage/release/import). The
            # leader surfaces its own failure to the caller and keeps
            # serving; mirror that here — bodies are written so partial
            # effects stay rank-consistent (import votes over the mesh).
            try:
                core.run_op(op["name"], op["args"])
            except Exception:
                log.exception("replayed exec op %r failed", op["name"])
        elif kind == "step":
            # Mirror the leader's engine-fatal handling: a deterministic
            # step error raises HERE too (identical programs); wipe and keep
            # replaying so the leader's own fail_all + recovery still has a
            # live follower. A crash instead would kill this rank before the
            # fail_all frame even arrives.
            try:
                core.set_step_time(op.get("now"))
                nxt = core.step_begin() if core.has_work() else None
                if pending is not None:
                    core.step_finalize(pending)
                    # A follower posts to nobody; close the first-token
                    # stamps and the step's gaps so they do not pile up.
                    core.outputs_posted(pending)
                pending = nxt
            except Exception as exc:
                log.exception("follower step failed; wiping in-flight state")
                pending = None
                core.fail_all(str(exc))
        elif kind == "fail_all":
            # Mirror the leader's engine-fatal wipe (AsyncJaxEngine._run).
            pending = None
            core.fail_all(op.get("error", "leader fail_all"))
        else:
            raise RuntimeError(f"unknown multihost op {kind!r}")
    if pending is not None:
        core.step_finalize(pending)
    log.info("leader disconnected; follower loop done")


# Every EngineConfig field that shapes the compiled XLA programs or the
# scheduler's decisions — the set every rank of one SPMD engine must agree
# on. ONE list, consumed by both leader_hello and engine_config_from_hello,
# so a new field can't be added to one side and silently default on the
# other.
_HELLO_FIELDS = (
    "model", "dtype", "attn_impl", "allow_random_weights", "quantization",
    "kv_dtype", "num_blocks", "block_size",
    "max_batch_size", "max_model_len", "prefill_chunk", "max_tokens_per_step",
    "decode_bucket", "seed", "enable_prefix_caching",
    "dp", "pp", "tp", "ep", "sp", "pp_microbatches",
    # KVBM tiers shape scheduling (onboarded blocks change prefill shapes):
    # every rank must run the same tier config in lockstep. remote_kv_addr
    # rides along so followers build the same G4 tier — its per-rank
    # hit/miss nondeterminism is handled by the onboard plan vote
    # (kvbm/offload.py OffloadManager.vote_plans).
    "host_kv_blocks", "disk_kv_path", "disk_kv_bytes", "remote_kv_addr",
    # Speculative decoding partitions decode batches into verify/plain rows
    # — a proposal mismatch across ranks would desync dispatch shapes.
    "spec_ngram", "spec_k",
)


def leader_hello(engine_cfg) -> dict:
    """The engine essentials every rank must agree on, as resolved by the
    leader (num_blocks may have been auto-sized from ITS device memory).
    Bucket ladders and dtype/attn choices shape the compiled dispatches —
    a mismatch means different XLA programs across ranks and hung
    collectives."""
    out = {"op": "hello"}
    for f in _HELLO_FIELDS:
        v = getattr(engine_cfg, f)
        out[f] = list(v) if isinstance(v, tuple) else v
    return out


def engine_config_from_hello(hello: dict):
    """Build the follower's EngineConfig from the leader's hello frame."""
    from dynamo_tpu.utils.config import EngineConfig

    kw = {f: hello[f] for f in _HELLO_FIELDS}
    kw["decode_bucket"] = tuple(kw["decode_bucket"])
    return EngineConfig(**kw)
