"""Multi-host engine: 2 processes x 2 virtual CPU devices = one 4-device
SPMD engine (reference: MultiNodeConfig, lib/llm/src/engines.rs:29-44).

The leader (rank 0) serves through the production AsyncJaxEngine loop while
broadcasting its op stream; the follower replays it. The leader's emitted
token streams must equal a single-process 4-device run of the identical
workload — proof the replicated state machines and the cross-process
collectives (Gloo on CPU; ICI/DCN on TPU) compute the same thing.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

RANK_SCRIPT = str(Path(__file__).parent / "multihost_rank.py")
REPO = str(Path(__file__).parent.parent)


def _env(n_local_devices: int = 2) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_local_devices}"
    ).strip()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _parse_result(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line in output:\n{stdout[-2000:]}")


@pytest.mark.slow
def test_two_process_engine_matches_single_process():
    port = _free_port()
    follower = subprocess.Popen(
        [sys.executable, RANK_SCRIPT, "1", str(port)], env=_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        leader = subprocess.run(
            [sys.executable, RANK_SCRIPT, "0", str(port)], env=_env(),
            capture_output=True, text=True, timeout=420)
        f_out, _ = follower.communicate(timeout=60)
    finally:
        if follower.poll() is None:
            follower.kill()
    assert leader.returncode == 0, (
        f"leader failed rc={leader.returncode}\nstdout:{leader.stdout[-1500:]}"
        f"\nstderr:{leader.stderr[-1500:]}")
    multi = _parse_result(leader.stdout)
    assert follower.returncode == 0 and "FOLLOWER_DONE" in f_out, (
        f"follower failed rc={follower.returncode}:\n{f_out[-1500:]}")

    ref = subprocess.run(
        [sys.executable, RANK_SCRIPT, "0", "0", "single"], env=_env(4),
        capture_output=True, text=True, timeout=420)
    assert ref.returncode == 0, ref.stderr[-1500:]
    single = _parse_result(ref.stdout)

    assert set(multi) == {"mh0", "mh1", "mh2"}
    for rid in single:
        assert multi[rid] == single[rid], f"stream {rid} diverged across hosts"
        assert len(multi[rid]) == 6 + int(rid[-1])  # exact max_tokens each


@pytest.mark.slow
def test_two_process_engine_kvbm_tiers():
    """Distributed KVBM (reference: block_manager/distributed/ leader.rs:126,
    worker.rs:143): each rank offloads/onboards its LOCAL cache shard in SPMD
    lockstep. The leader's streams must match a single-process run of the
    same tiered workload, with blocks actually cycled through the host tier
    on both ranks."""
    port = _free_port()
    follower = subprocess.Popen(
        [sys.executable, RANK_SCRIPT, "1", str(port)], env=_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        leader = subprocess.run(
            [sys.executable, RANK_SCRIPT, "0", str(port), "kvbm"], env=_env(),
            capture_output=True, text=True, timeout=420)
        f_out, _ = follower.communicate(timeout=60)
    finally:
        if follower.poll() is None:
            follower.kill()
    assert leader.returncode == 0, (
        f"leader failed rc={leader.returncode}\nstdout:{leader.stdout[-1500:]}"
        f"\nstderr:{leader.stderr[-1500:]}")
    multi = _parse_result(leader.stdout)
    assert follower.returncode == 0 and "FOLLOWER_DONE" in f_out, (
        f"follower failed rc={follower.returncode}:\n{f_out[-1500:]}")

    ref = subprocess.run(
        [sys.executable, RANK_SCRIPT, "0", "0", "single-kvbm"], env=_env(4),
        capture_output=True, text=True, timeout=420)
    assert ref.returncode == 0, ref.stderr[-1500:]
    single = _parse_result(ref.stdout)

    # the offload/onboard cycle actually happened, identically in both runs
    assert multi["offloaded"] > 0 and multi["onboarded"] > 0
    assert multi["offloaded"] == single["offloaded"]
    assert multi["onboarded"] == single["onboarded"]
    # bit-identical greedy continuation after the tier round trip,
    # and across multi-process vs single-process execution
    assert multi["a2"] == multi["a1"]
    assert multi["a1"] == single["a1"] and multi["a2"] == single["a2"]


@pytest.mark.slow
def test_two_process_engine_g4_remote_tier():
    """Multi-host x G4: both ranks offload to / onboard from ONE shared
    remote store (per-rank shard namespaces), with onboard plans voted to
    the mesh-wide minimum so shared-store nondeterminism can't desync the
    ranks. Streams + tier counters must match a single-process run against
    the same store."""
    import re
    import subprocess as sp

    store = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.components.kv_store",
         "--host", "127.0.0.1", "--port", "0", "--capacity-gib", "0.5"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        line = ""
        for line in store.stdout:  # type: ignore[union-attr]
            if "KV_STORE_READY" in line:
                break
        m = re.search(r"port=(\d+)", line)
        assert m, f"no store port in {line!r}"
        addr = f"127.0.0.1:{m.group(1)}"

        env = _env()
        env["DYN_TEST_STORE_ADDR"] = addr
        port = _free_port()
        follower = subprocess.Popen(
            [sys.executable, RANK_SCRIPT, "1", str(port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            leader = subprocess.run(
                [sys.executable, RANK_SCRIPT, "0", str(port), "kvbm-remote"],
                env=env, capture_output=True, text=True, timeout=420)
            f_out, _ = follower.communicate(timeout=60)
        finally:
            if follower.poll() is None:
                follower.kill()
        assert leader.returncode == 0, (
            f"leader failed rc={leader.returncode}\nstdout:{leader.stdout[-1500:]}"
            f"\nstderr:{leader.stderr[-1500:]}")
        multi = _parse_result(leader.stdout)
        assert follower.returncode == 0 and "FOLLOWER_DONE" in f_out, (
            f"follower failed rc={follower.returncode}:\n{f_out[-1500:]}")

        ref = subprocess.run(
            [sys.executable, RANK_SCRIPT, "0", "0", "single-kvbm-remote"],
            env={**_env(4), "DYN_TEST_STORE_ADDR": addr},
            capture_output=True, text=True, timeout=420)
        assert ref.returncode == 0, ref.stderr[-1500:]
        single = _parse_result(ref.stdout)
    finally:
        store.kill()
        try:
            store.communicate(timeout=10)
        except sp.TimeoutExpired:
            pass

    assert multi["offloaded"] > 0 and multi["onboarded"] > 0
    assert multi["offloaded"] == single["offloaded"]
    assert multi["onboarded"] == single["onboarded"]
    assert multi["a2"] == multi["a1"]
    assert multi["a1"] == single["a1"] and multi["a2"] == single["a2"]


@pytest.mark.slow
def test_multihost_disagg_prefill_to_decode(tmp_path):
    """The north-star composition (reference: recipes/llama-3-70b/vllm/
    disagg-multi-node/deploy.yaml:36-71): a 2-process prefill engine stages
    KV on BOTH ranks (replayed kv_stage op, per-rank shard servers), a
    2-process decode engine pulls it (each rank fetching its own box slices
    inside the replayed kv_import op) and generates — bit-identical to a
    single-process aggregated run."""
    p_port, d_port = _free_port(), _free_port()
    params_file = str(tmp_path / "params.json")
    done_file = str(tmp_path / "done")
    env = _env()
    env["DYN_TEST_PARAMS_FILE"] = params_file
    env["DYN_TEST_DONE_FILE"] = done_file

    procs = {
        "p1": subprocess.Popen([sys.executable, RANK_SCRIPT, "1", str(p_port)],
                               env=env, stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT, text=True),
        "d1": subprocess.Popen([sys.executable, RANK_SCRIPT, "1", str(d_port)],
                               env=env, stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT, text=True),
        "p0": subprocess.Popen([sys.executable, RANK_SCRIPT, "0", str(p_port),
                                "disagg-prefill"], env=env,
                               stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT, text=True),
    }
    try:
        decode = subprocess.run(
            [sys.executable, RANK_SCRIPT, "0", str(d_port), "disagg-decode"],
            env=env, capture_output=True, text=True, timeout=420)
        outs = {}
        for name, p in procs.items():
            out, _ = p.communicate(timeout=120)
            outs[name] = out
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    assert decode.returncode == 0, (
        f"decode leader failed rc={decode.returncode}\n"
        f"stdout:{decode.stdout[-2000:]}\nstderr:{decode.stderr[-2000:]}")
    d_res = _parse_result(decode.stdout)
    p_res = _parse_result(outs["p0"])
    assert p_res["staged_shards"] == 2
    # 5 blocks staged ((24-1)//4 — the last-token cap), all pulled+injected
    assert d_res["injected"] == 5, d_res
    for name in ("p1", "d1"):
        assert "FOLLOWER_DONE" in outs[name], f"{name}:\n{outs[name][-2000:]}"

    oracle = subprocess.run(
        [sys.executable, RANK_SCRIPT, "0", "0", "disagg-single"], env=_env(4),
        capture_output=True, text=True, timeout=420)
    assert oracle.returncode == 0, oracle.stderr[-1500:]
    single = _parse_result(oracle.stdout)
    assert d_res["dx"] == single["dx"], (
        f"disagg stream diverged: {d_res['dx']} != {single['dx']}")


def test_hello_carries_kvbm_tier_fields():
    """Tier config shapes scheduling (onboarded blocks change prefill
    shapes), so it must ride the hello frame to followers."""
    from dynamo_tpu.parallel import multihost as mh
    from dynamo_tpu.utils.config import EngineConfig

    cfg = EngineConfig(model="tiny-llama", host_kv_blocks=7,
                       disk_kv_path="/tmp/x", disk_kv_bytes=123)
    out = mh.engine_config_from_hello(mh.leader_hello(cfg))
    assert out.host_kv_blocks == 7
    assert out.disk_kv_path == "/tmp/x"
    assert out.disk_kv_bytes == 123
