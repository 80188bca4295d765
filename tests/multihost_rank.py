"""One rank of the 2-process multi-host engine test (spawned by
tests/test_multihost.py with JAX_PLATFORMS=cpu and 2 virtual devices per
process → a 4-device global mesh).

rank 0: leader — serves 3 requests through AsyncJaxEngine (the production
pipelined loop) while broadcasting the op stream; prints the collected
token streams as JSON.
rank 1: follower — replays the op stream through follower_loop.

Usage: python multihost_rank.py <rank> <coordinator_port> [mode]
mode "single": no jax.distributed — a 4-device single-process reference run
of the same workload (the equality oracle for the leader's output).
"""

from __future__ import annotations

import asyncio
import faulthandler
faulthandler.dump_traceback_later(500, exit=True)
import dataclasses
import json
import sys
from pathlib import Path

from dynamo_tpu.engine.engine import AsyncJaxEngine, EngineCore
from dynamo_tpu.parallel import multihost as mh
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.utils.config import EngineConfig


def engine_cfg(kvbm: bool = False, remote_addr: str | None = None) -> EngineConfig:
    return EngineConfig(
        model="tiny-llama",
        block_size=4,
        # kvbm/remote modes: a tight pool (12 usable blocks) so the fillers
        # evict prompt A into the tier and the re-run onboards it.
        num_blocks=13 if (kvbm or remote_addr) else 64,
        max_batch_size=8,
        max_model_len=128,
        prefill_chunk=32,
        decode_bucket=(4, 8),
        tp=2,   # tiny-llama has 2 kv heads; model axis must divide them
        dp=2,
        host_kv_blocks=64 if kvbm else 0,
        # remote-only tier: every eviction rides to the shared G4 store
        # (per-rank shard namespaces), onboards come back from it.
        remote_kv_addr=remote_addr,
    )


def make_reqs() -> list[PreprocessedRequest]:
    reqs = []
    for i in range(3):
        r = PreprocessedRequest(
            token_ids=[3 * i + j for j in range(5 + i)],
            stop_conditions=StopConditions(max_tokens=6 + i, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        r.request_id = f"mh{i}"
        reqs.append(r)
    return reqs


async def run_kvbm_workload(engine: AsyncJaxEngine) -> dict:
    """Evict → offload → onboard through the (possibly sharded) host tier:
    prompt A, disjoint fillers that churn A out of the device pool, prompt A
    again. Returns both A streams plus the kvbm counters."""
    async def one(req: PreprocessedRequest) -> list[int]:
        toks: list[int] = []
        async for out in engine.generate(req):
            toks.extend(out.token_ids)
        return toks

    def req(prompt: list[int], rid: str, max_tokens: int) -> PreprocessedRequest:
        r = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        r.request_id = rid
        return r

    prompt_a = [(100 + i) % 250 for i in range(24)]  # 6 blocks of 4
    first = await one(req(prompt_a, "a1", 6))
    for i in range(4):
        await one(req([(200 + 30 * i + j) % 250 for j in range(24)], f"f{i}", 4))
    second = await one(req(prompt_a, "a2", 6))
    kvbm = engine.core.kvbm
    assert kvbm is not None
    return {"a1": first, "a2": second,
            "offloaded": kvbm.stats.offloaded_blocks,
            "onboarded": kvbm.stats.onboarded_blocks}


async def leader(coord_port: int, kvbm: bool = False,
                 remote_addr: str | None = None) -> None:
    mn = mh.MultiNodeConfig(num_nodes=2, node_rank=0,
                            leader_addr=f"127.0.0.1:{coord_port}")
    mh.initialize_distributed(mn)
    channel = mh.LeaderOpChannel(mn.resolved_op_port(), num_followers=1)
    await asyncio.get_running_loop().run_in_executor(None, channel.accept_followers, 120.0)

    cfg = engine_cfg(kvbm, remote_addr)
    core = EngineCore(cfg)
    channel.broadcast(mh.leader_hello(
        dataclasses.replace(cfg, num_blocks=core.runner.spec.num_blocks)))
    await asyncio.get_running_loop().run_in_executor(None, channel.wait_ready)
    engine = AsyncJaxEngine(core, op_sink=channel.broadcast)

    if kvbm or remote_addr:
        out = await run_kvbm_workload(engine)
        await engine.shutdown()
        channel.close()
        print("RESULT " + json.dumps(out), flush=True)
        return

    async def one(req: PreprocessedRequest) -> list[int]:
        toks: list[int] = []
        async for out in engine.generate(req):
            toks.extend(out.token_ids)
        return toks

    results = await asyncio.gather(*(one(r) for r in make_reqs()))
    await engine.shutdown()
    channel.close()
    print("RESULT " + json.dumps({r.request_id: t for r, t in zip(make_reqs(), results)}),
          flush=True)


def follower(coord_port: int) -> None:
    mn = mh.MultiNodeConfig(num_nodes=2, node_rank=1,
                            leader_addr=f"127.0.0.1:{coord_port}")
    mh.initialize_distributed(mn)
    sock = mh.connect_to_leader("127.0.0.1", mn.resolved_op_port(), timeout=120.0)

    def core_factory(hello: dict) -> EngineCore:
        return EngineCore(mh.engine_config_from_hello(hello))

    mh.follower_loop(core_factory, sock)
    print("FOLLOWER_DONE", flush=True)


# -- multi-host x disagg: 2-proc prefill engine → 2-proc decode engine -------
# (reference: recipes/llama-3-70b/vllm/disagg-multi-node/deploy.yaml:36-71 —
# multi-node prefill and decode pools with NIXL KV handoff between them)

DISAGG_PROMPT = list(range(60, 84))  # 24 tokens = 6 blocks of 4


def _disagg_req(max_tokens: int) -> PreprocessedRequest:
    r = PreprocessedRequest(
        token_ids=list(DISAGG_PROMPT),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
    r.request_id = "dx"
    return r


class _Ctx:
    def is_cancelled(self):
        return False


async def disagg_prefill_leader(coord_port: int, params_file: str,
                                done_file: str) -> None:
    """Leader of the 2-process PREFILL engine: serve one prefill, stage the
    KV on both ranks, publish kv_transfer_params, hold until the decode
    group acks done."""
    import os

    from dynamo_tpu.disagg.handlers import PrefillHandler
    from dynamo_tpu.disagg.source import KvTransferSource

    mn = mh.MultiNodeConfig(num_nodes=2, node_rank=0,
                            leader_addr=f"127.0.0.1:{coord_port}")
    mh.initialize_distributed(mn)
    channel = mh.LeaderOpChannel(mn.resolved_op_port(), num_followers=1)
    await asyncio.get_running_loop().run_in_executor(None, channel.accept_followers, 120.0)

    cfg = engine_cfg()
    core = EngineCore(cfg)
    hello = mh.leader_hello(
        dataclasses.replace(cfg, num_blocks=core.runner.spec.num_blocks))
    hello["disagg_role"] = "prefill"  # followers bind shard servers
    channel.broadcast(hello)
    infos = await asyncio.get_running_loop().run_in_executor(None, channel.wait_ready)
    engine = AsyncJaxEngine(core, op_sink=channel.broadcast)

    source = KvTransferSource(
        engine, advertise_host="127.0.0.1",
        extra_shards=[{"addr": i["shard_addr"], "box": i["shard_box"]}
                      for i in infos if "shard_addr" in i])
    source.start()
    prefill = PrefillHandler(engine, source, block_size=cfg.block_size)
    outs = []
    async for item in prefill.generate(_disagg_req(6).to_dict(), _Ctx()):
        outs.append(item)
    params = outs[-1]["kv_transfer_params"]
    assert len(params["shards"]) == 2, params["shards"]
    tmp = params_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump(params, f)
    os.replace(tmp, params_file)  # atomic: the decode group polls this path

    for _ in range(600):  # hold the engine alive while decode pulls
        if Path(done_file).exists():
            break
        await asyncio.sleep(0.2)
    await source.stop()
    await engine.shutdown()
    channel.close()
    print("RESULT " + json.dumps({"staged_shards": len(params["shards"])}),
          flush=True)


async def disagg_decode_leader(coord_port: int, params_file: str,
                               done_file: str) -> None:
    """Leader of the 2-process DECODE engine: pull the staged KV (each rank
    fetches its own box slices inside the replayed kv_import op), then
    generate — the stream must be bit-identical to an aggregated run."""
    from dynamo_tpu.disagg.receiver import pull_and_import

    mn = mh.MultiNodeConfig(num_nodes=2, node_rank=0,
                            leader_addr=f"127.0.0.1:{coord_port}")
    mh.initialize_distributed(mn)
    channel = mh.LeaderOpChannel(mn.resolved_op_port(), num_followers=1)
    await asyncio.get_running_loop().run_in_executor(None, channel.accept_followers, 120.0)

    cfg = engine_cfg()
    core = EngineCore(cfg)
    channel.broadcast(mh.leader_hello(
        dataclasses.replace(cfg, num_blocks=core.runner.spec.num_blocks)))
    await asyncio.get_running_loop().run_in_executor(None, channel.wait_ready)
    engine = AsyncJaxEngine(core, op_sink=channel.broadcast)

    params = None
    for _ in range(600):
        if Path(params_file).exists():
            with open(params_file) as f:
                params = json.load(f)
            break
        await asyncio.sleep(0.2)
    assert params is not None, "prefill group never published params"

    injected = await pull_and_import(engine, params)

    toks: list[int] = []
    async for out in engine.generate(_disagg_req(6)):
        toks.extend(out.token_ids)
    Path(done_file).touch()
    await engine.shutdown()
    channel.close()
    print("RESULT " + json.dumps({"injected": injected, "dx": toks}), flush=True)


async def disagg_single() -> None:
    """4-device single-process AGGREGATED oracle for the disagg stream."""
    engine = AsyncJaxEngine(EngineCore(engine_cfg()))
    toks: list[int] = []
    async for out in engine.generate(_disagg_req(6)):
        toks.extend(out.token_ids)
    await engine.shutdown()
    print("RESULT " + json.dumps({"dx": toks}), flush=True)


async def single(kvbm: bool = False, remote_addr: str | None = None) -> None:
    """Single-process 4-device reference run of the same workload."""
    engine = AsyncJaxEngine(EngineCore(engine_cfg(kvbm, remote_addr)))

    if kvbm or remote_addr:
        out = await run_kvbm_workload(engine)
        await engine.shutdown()
        print("RESULT " + json.dumps(out), flush=True)
        return

    async def one(req: PreprocessedRequest) -> list[int]:
        toks: list[int] = []
        async for out in engine.generate(req):
            toks.extend(out.token_ids)
        return toks

    results = await asyncio.gather(*(one(r) for r in make_reqs()))
    await engine.shutdown()
    print("RESULT " + json.dumps({r.request_id: t for r, t in zip(make_reqs(), results)}),
          flush=True)


if __name__ == "__main__":
    rank = int(sys.argv[1])
    port = int(sys.argv[2])
    mode = sys.argv[3] if len(sys.argv) > 3 else "multi"
    import os

    if mode == "single":
        asyncio.run(single())
    elif mode == "single-kvbm":
        asyncio.run(single(kvbm=True))
    elif mode == "single-kvbm-remote":
        asyncio.run(single(remote_addr=os.environ["DYN_TEST_STORE_ADDR"]))
    elif mode == "disagg-single":
        asyncio.run(disagg_single())
    elif mode in ("disagg-prefill", "disagg-decode") and rank == 0:
        params_file = os.environ["DYN_TEST_PARAMS_FILE"]
        done_file = os.environ["DYN_TEST_DONE_FILE"]
        fn = (disagg_prefill_leader if mode == "disagg-prefill"
              else disagg_decode_leader)
        asyncio.run(fn(port, params_file, done_file))
    elif rank == 0:
        asyncio.run(leader(
            port, kvbm=(mode == "kvbm"),
            remote_addr=(os.environ["DYN_TEST_STORE_ADDR"]
                         if mode == "kvbm-remote" else None)))
    else:
        follower(port)
