"""Session-sticky KV retention (engine/session.py): wire helpers, the
SessionStore pin lifecycle against a raw PrefixPool, the engine e2e
contract — turn 2 token-identical to cold recompute while prefilling only
the new suffix, with the avoided-tokens counter measuring exactly turn 1's
committed context — TTL expiry, host-tier demotion + re-import after
device eviction, chaos-injected offload faults, zero leaked pins, router
session affinity with dead-holder fallback, and the mocker mirror.
"""

import time

import pytest

from dynamo_tpu.engine.prefix_pool import PrefixPool
from dynamo_tpu.engine.session import (
    SESSION_KEY,
    SessionStore,
    get_session_metrics,
    session_id_from,
    session_id_of,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

BS = 4  # engine block size used throughout


# ---------------------------------------------------------------------------
# Wire helpers
# ---------------------------------------------------------------------------

def test_session_id_wire_extraction():
    assert session_id_from({"x-session-id": "h"}, {"session_id": "b"}) == "h"
    assert session_id_from({}, {"session_id": "b"}) == "b"
    assert session_id_from({"x-session-id": "  "}, {}) is None
    assert session_id_from(None, None) is None
    assert session_id_of({SESSION_KEY: "s1"}) == "s1"
    assert session_id_of({SESSION_KEY: ""}) is None
    assert session_id_of(None) is None


# ---------------------------------------------------------------------------
# SessionStore against a raw pool
# ---------------------------------------------------------------------------

def _committed_chain(pool: PrefixPool, n: int, base_hash: int = 100):
    """Allocate+commit an n-block chain; returns (block_ids, hashes).
    The caller still holds the allocation refs (like a live seq)."""
    bids = pool.allocate(n)
    parent = None
    hashes = []
    for i, bid in enumerate(bids):
        h = base_hash + i
        pool.commit(bid, h, parent)
        parent = h
        hashes.append(h)
    return bids, hashes


def test_session_store_pin_lifecycle():
    pool = PrefixPool(num_blocks=16, block_size=BS)
    free0 = pool.num_free
    store = SessionStore(pool, ttl=60.0)
    bids, hashes = _committed_chain(pool, 3)

    # Retain BEFORE the seq's refs drop (the engine's ordering): the pins
    # keep the chain active through the handoff.
    entry = store.retain("s1", hashes, now=0.0)
    assert entry is not None and entry.pinned == bids
    pool.release(bids)  # the seq finishes
    assert pool.num_free == free0 - 3  # pinned ⇒ not free, not inactive
    assert store.pinned_blocks == 3

    # Claim releases the pins into the matchable inactive pool…
    assert store.claim("s1", now=1.0) is not None
    assert len(store) == 0
    assert pool.num_free == free0
    # …where an admission-time match re-references the same blocks.
    assert pool.match_prefix(hashes) == bids
    pool.release(bids)


def test_session_store_ttl_and_lru_capacity():
    pool = PrefixPool(num_blocks=32, block_size=BS)
    store = SessionStore(pool, ttl=10.0, max_sessions=2)
    for i, sid in enumerate(("a", "b", "c")):
        _, hashes = _committed_chain(pool, 2, base_hash=100 * (i + 1))
        store.retain(sid, hashes, now=float(i))
    assert len(store) == 3  # caller enforces max_sessions via pop_oldest
    sid, entry = store.pop_oldest()
    assert sid == "a"
    pool.release(entry.pinned)
    # TTL: only "b" (retained at t=1) is stale at t=11.5.
    expired = store.pop_expired(now=11.5)
    assert [s for s, _ in expired] == ["b"]
    for _, e in expired:
        pool.release(e.pinned)
    assert len(store) == 1 and store.claim("c", now=12.0) is not None


def test_session_store_retain_nothing_committed():
    pool = PrefixPool(num_blocks=8, block_size=BS)
    store = SessionStore(pool, ttl=60.0)
    assert store.retain("s", [], now=0.0) is None
    assert len(store) == 0


# ---------------------------------------------------------------------------
# Engine e2e
# ---------------------------------------------------------------------------

def _make_core(**kw):
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.utils.config import EngineConfig

    base = dict(model="tiny-llama", max_batch_size=2, max_model_len=128,
                num_blocks=64, block_size=BS, dtype="float32",
                enable_prefix_caching=True, session_ttl=600.0,
                session_tiers=False)
    base.update(kw)
    return EngineCore(EngineConfig(**base))


def _generate(core, toks, session_id=None, max_tokens=4):
    ann = {SESSION_KEY: session_id} if session_id else {}
    core.add_request(PreprocessedRequest(
        token_ids=list(toks), annotations=ann,
        sampling_options=SamplingOptions(temperature=0.0),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True)))
    out = []
    while core.has_work():
        for o in core.step().values():
            out.extend(o.token_ids)
    return out


def test_engine_turn2_suffix_only_and_token_identical():
    """The tentpole contract: turn 2 under the same session id produces
    exactly the tokens a cold engine recomputing the full prompt would,
    prefills only the new suffix, and the avoided-tokens counter equals
    turn 1's committed context length — measured, not estimated."""
    core = _make_core()
    free0 = core.pool.num_free
    sm = get_session_metrics()
    base_avoided = sm.avoided_tokens.get()
    base_hits = sm.hits.get()

    p1 = list(range(1, 17))  # 16 tokens = 4 blocks
    out1 = _generate(core, p1, "s1")
    assert len(out1) == 4
    # The final sampled token's KV is never written, so the committed (and
    # retainable) context is the block-aligned prefix of turn1-1 tokens.
    committed_tokens = ((len(p1) + len(out1) - 1) // BS) * BS
    snap = core.sessions.snapshot()
    assert snap["sessions"] == 1
    assert snap["retained_tokens"] == committed_tokens

    pre_prefill = core.metrics.num_prefill_tokens
    p2 = p1 + out1 + [3, 1, 4, 1, 5, 9, 2, 6]
    out2 = _generate(core, p2, "s1")
    avoided = sm.avoided_tokens.get() - base_avoided
    assert avoided == committed_tokens
    assert sm.hits.get() - base_hits == 1
    # Suffix-only prefill: the engine computed exactly the unmatched tail.
    assert core.metrics.num_prefill_tokens - pre_prefill == len(p2) - avoided

    cold = _make_core()
    assert out2 == _generate(cold, p2)

    # Zero leaked pins: dropping every session returns the pool to baseline
    # (num_free counts free + inactive, so any stuck ref would show).
    core.sessions.release_all()
    assert core.pool.num_free == free0


def test_engine_session_ttl_expiry_releases_pins():
    core = _make_core(session_ttl=0.01)
    free0 = core.pool.num_free
    sm = get_session_metrics()
    base_expired = sm.expired.get()
    _generate(core, list(range(1, 17)), "s1")
    assert len(core.sessions) == 1
    time.sleep(0.05)
    # Any traffic drives step_begin → the TTL sweep.
    _generate(core, list(range(40, 56)))
    assert len(core.sessions) == 0
    assert sm.expired.get() - base_expired == 1
    assert core.pool.num_free == free0


def test_engine_session_not_retained_on_cancel():
    """CANCELLED/ERROR streams must not park KV in the session store."""
    core = _make_core()
    core.add_request(PreprocessedRequest(
        request_id="c1", token_ids=list(range(1, 17)),
        annotations={SESSION_KEY: "s1"},
        sampling_options=SamplingOptions(temperature=0.0),
        stop_conditions=StopConditions(max_tokens=8, ignore_eos=True)))
    core.step()  # prefill + first decode
    core.abort("c1")
    while core.has_work():
        core.step()
    assert len(core.sessions) == 0


def test_engine_session_demotion_reimport_after_device_eviction():
    """session_tiers write-through: an expired session's chain lands in the
    KVBM host tier; after the device copies are evicted, turn 2 re-imports
    from the host ladder and still matches a cold recompute."""
    core = _make_core(session_ttl=0.01, session_tiers=True, host_kv_blocks=32)
    sm = get_session_metrics()
    base_dem = sm.demoted_blocks.get()
    p1 = list(range(1, 17))
    out1 = _generate(core, p1, "s1")
    time.sleep(0.05)
    _generate(core, list(range(40, 56)))  # sweep → demote to host tier
    assert len(core.sessions) == 0
    staged = sm.demoted_blocks.get() - base_dem
    assert staged > 0
    assert len(core.kvbm.tiers[0]) >= staged

    # Evict every inactive device copy (allocate churns the whole free+LRU
    # pool), so turn 2 can only win via the host-tier import.
    bids = core.pool.allocate(core.pool.num_free)
    core.pool.release(bids)
    p2 = p1 + out1 + [3, 1, 4, 1, 5]
    out2 = _generate(core, p2, "s1")
    cold = _make_core()
    assert out2 == _generate(cold, p2)


@pytest.mark.chaos
def test_engine_session_demotion_chaos_offload_fault(chaos_seed):
    """A chaos fault at kvbm.offload during session demotion must not leak
    pins or kill the engine: the staging rolls back, the pins still
    release, and later turns still produce cold-identical tokens."""
    from dynamo_tpu import chaos

    chaos.configure({"seed": chaos_seed, "rules": [
        {"point": "kvbm.offload", "kind": "error", "rate": 1.0, "count": 1},
    ]})
    core = _make_core(session_ttl=0.01, session_tiers=True, host_kv_blocks=32)
    free0 = core.pool.num_free
    p1 = list(range(1, 17))
    out1 = _generate(core, p1, "s1")
    time.sleep(0.05)
    _generate(core, list(range(40, 56)))  # sweep → demote hits the fault
    assert len(core.sessions) == 0  # session dropped despite the fault
    p2 = p1 + out1 + [5, 5, 5]
    out2 = _generate(core, p2, "s1")
    cold = _make_core()
    assert out2 == _generate(cold, p2)
    core.sessions.release_all()
    assert core.pool.num_free == free0


# ---------------------------------------------------------------------------
# Router session affinity
# ---------------------------------------------------------------------------

def test_router_session_affinity_and_dead_holder_fallback():
    from dynamo_tpu.router.kv_router import KvRouter, KvRouterConfig

    r = KvRouter(KvRouterConfig(block_size=4))
    tokens = list(range(10, 30))
    wid, _ = r.find_best_match("r1", tokens, worker_ids=[1, 2],
                               session_id="sess")
    r.complete("r1")
    assert r.session_affinity["sess"] == wid
    # Turn 2 short-circuits to the recorded holder.
    wid2, _ = r.find_best_match("r2", tokens, worker_ids=[1, 2],
                                session_id="sess")
    assert wid2 == wid
    r.complete("r2")
    # Worker death: affinity purged, the request re-arbitrates among the
    # living (arbiter pull/recompute pricing or the classic scheduler).
    r.remove_worker(wid)
    assert "sess" not in r.session_affinity
    other = 2 if wid == 1 else 1
    wid3, _ = r.find_best_match("r3", tokens, worker_ids=[other],
                                session_id="sess")
    assert wid3 == other
    r.complete("r3")
    assert r.session_affinity["sess"] == other  # re-pinned to the new home


def test_router_session_affinity_bounded():
    from dynamo_tpu.router.kv_router import KvRouter, KvRouterConfig

    r = KvRouter(KvRouterConfig(block_size=4))
    r.max_sessions = 4
    for i in range(8):
        r.find_best_match(f"r{i}", list(range(10, 18)), worker_ids=[1],
                          session_id=f"s{i}")
        r.complete(f"r{i}")
    assert len(r.session_affinity) == 4
    assert "s0" not in r.session_affinity and "s7" in r.session_affinity


# ---------------------------------------------------------------------------
# Mocker mirror
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_mock_engine_session_retention():
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineArgs

    eng = MockEngine(MockEngineArgs(
        num_blocks=64, block_size=16, enable_prefix_caching=True,
        session_ttl=30.0, speedup_ratio=1000.0))
    sm = get_session_metrics()
    base_hits, base_avoided = sm.hits.get(), sm.avoided_tokens.get()

    async def turn(toks):
        out = []
        async for d in eng.generate(PreprocessedRequest(
                token_ids=list(toks), annotations={SESSION_KEY: "m1"},
                stop_conditions=StopConditions(max_tokens=4,
                                               ignore_eos=True))):
            out.extend(d.token_ids)
        return out

    p1 = list(range(1, 65))
    out1 = await turn(p1)
    snap = eng.stats()["session"]
    assert snap["sessions"] == 1 and snap["pinned_blocks"] > 0
    await turn(p1 + out1 + list(range(100, 132)))
    assert sm.hits.get() - base_hits == 1
    assert sm.avoided_tokens.get() - base_avoided > 0
    await eng.stop()


@pytest.mark.slow
def test_session_turn2_in_a_mixed_step_matches_solo():
    """Session-retained turn 2 (suffix-only prefill riding a mixed step next
    to a live decode row) emits the streams it emits when the sibling has
    finished before it arrives, so that no step mixes phases."""
    def run(together):
        core = _make_core(max_batch_size=4)
        p1 = list(range(1, 17))
        out1 = _generate(core, p1, "s1")
        # A sibling stream decodes while turn 2's suffix prefill lands.
        sib = PreprocessedRequest(
            token_ids=list(range(60, 68)),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=16, ignore_eos=True))
        sib.request_id = "sib"
        core.add_request(sib)
        got = {"sib": [], "t2": []}
        for _ in range(2 if together else 100):
            for rid, o in core.step().items():
                got[rid].extend(o.token_ids)
        assert core.has_work() == together
        p2 = p1 + out1 + [3, 1, 4, 1, 5, 9, 2, 6]
        t2 = PreprocessedRequest(
            token_ids=p2, annotations={SESSION_KEY: "s1"},
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True))
        t2.request_id = "t2"
        core.add_request(t2)
        while core.has_work():
            for rid, o in core.step().items():
                got[rid].extend(o.token_ids)
        return out1, got

    assert run(True) == run(False)
