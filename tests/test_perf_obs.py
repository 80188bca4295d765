"""Performance observability: analytic cost model, step profiler, perf
metrics family.

The cost-model tests pin the conventions documented in
obs/costmodel.py (matmul-only FLOPs, block-rounded attention, int8 KV
payload + scales) against hand-computed values — drift in either the
model or the convention fails loudly.
"""

from __future__ import annotations

import pytest

from dynamo_tpu.models.config import MODEL_PRESETS, resolve_model_config
from dynamo_tpu.obs import costmodel as cm
from dynamo_tpu.obs.compile_ledger import BucketSig
from dynamo_tpu.obs.profiler import (
    PerfMetrics,
    StepPerfProfiler,
    phase,
)
from dynamo_tpu.utils.metrics import MetricsRegistry

from tests.test_engine import make_req, run_to_completion, tiny_config


# ---------------------------------------------------------------------------
# Analytic cost model vs hand-computed values
# ---------------------------------------------------------------------------

def test_paged_attention_cost_bf16_hand_computed():
    # B=2 rows of 1 query token, H=4, KH=2, D=16, context 10 @ block 4:
    # 3 blocks DMA'd -> S = 12 block-rounded context positions.
    c = cm.paged_attention_cost(
        batch=2, q_tokens=1, num_heads=4, num_kv_heads=2, head_dim=16,
        kv_len=10, block_size=4, kv_dtype="bfloat16")
    assert c.flops == 4 * 2 * 1 * 4 * 16 * 12          # QK^T + PV matmuls
    q_bytes = 2 * 1 * 4 * 16 * 2                       # Q read (bf16)
    kv_bytes = 2 * 2 * 3 * (4 * 2 * 16 * 2)            # K and V, 3 blocks/row
    assert c.hbm_bytes == q_bytes + kv_bytes + q_bytes  # + output write


def test_paged_attention_cost_int8_halves_kv_payload():
    kw = dict(batch=2, q_tokens=1, num_heads=4, num_kv_heads=2, head_dim=16,
              kv_len=10, block_size=4)
    bf16 = cm.paged_attention_cost(kv_dtype="bfloat16", **kw)
    int8 = cm.paged_attention_cost(kv_dtype="int8", **kw)
    assert int8.flops == bf16.flops                     # same matmul volume
    # int8 block: half payload + per-(block, kv-head) f32 scales.
    kv_block = 4 * 2 * 16 * 1 + 2 * 4
    q_bytes = 2 * 1 * 4 * 16 * 2
    assert int8.hbm_bytes == 2 * q_bytes + 2 * 2 * 3 * kv_block
    assert int8.hbm_bytes < bf16.hbm_bytes


def test_paged_attention_cost_int4_quarters_kv_payload():
    kw = dict(batch=2, q_tokens=1, num_heads=4, num_kv_heads=2, head_dim=16,
              kv_len=10, block_size=4)
    bf16 = cm.paged_attention_cost(kv_dtype="bfloat16", **kw)
    int4 = cm.paged_attention_cost(kv_dtype="int4", **kw)
    assert int4.flops == bf16.flops                     # same matmul volume
    # int4 block: quarter payload (0.5 B/elem) + per-(block, kv-head) f32
    # scales — hand-computed like the int8 twin above.
    kv_block = 4 * 2 * 16 * 0.5 + 2 * 4
    q_bytes = 2 * 1 * 4 * 16 * 2
    assert int4.hbm_bytes == 2 * q_bytes + 2 * 2 * 3 * kv_block
    # The KV payload alone (scales excluded) is exactly 0.25x bf16's.
    bf16_kv_payload = bf16.hbm_bytes - 2 * q_bytes
    int4_kv_payload = int4.hbm_bytes - 2 * q_bytes - 2 * 2 * 3 * (2 * 4)
    assert int4_kv_payload == pytest.approx(0.25 * bf16_kv_payload)


def test_dense_matmul_cost_hand_computed():
    c = cm.dense_matmul_cost(8, 16, 32)
    assert c.flops == 2 * 8 * 16 * 32
    assert c.hbm_bytes == (8 * 32 + 32 * 16 + 8 * 16) * 2
    assert c.intensity == pytest.approx(c.flops / c.hbm_bytes)


def test_kernel_cost_roofline_bound():
    hw = cm.HardwareSpec("x", peak_flops=100.0, hbm_bw=10.0)  # ridge = 10
    bw_bound = cm.KernelCost("a", flops=50.0, hbm_bytes=20.0)  # intensity 2.5
    compute = cm.KernelCost("b", flops=500.0, hbm_bytes=10.0)  # intensity 50
    assert bw_bound.bound(hw) == "bandwidth"
    assert compute.bound(hw) == "compute"
    assert bw_bound.time_bound(hw) == pytest.approx(2.0)   # 20B / 10 B/s
    assert compute.time_bound(hw) == pytest.approx(5.0)    # 500F / 100 F/s


def test_decode_step_cost_composition():
    """The per-phase decomposition recomposes to the closed-form totals."""
    cfg = resolve_model_config("tiny-llama")
    batch, kv_len, bs = 4, 10, 4
    phases = cm.decode_step_cost(cfg, batch=batch, kv_len=kv_len,
                                 block_size=bs)
    h, L = cfg.hidden_size, cfg.num_layers
    s = 12  # ceil(10/4) * 4
    assert phases["attention"].flops == (
        4 * cfg.num_heads * cfg.head_dim * batch * s * L)
    assert phases["proj"].flops == (
        2 * batch * h * (2 * cfg.q_size + 2 * cfg.kv_size) * L)
    assert phases["mlp"].flops == 6 * batch * h * cfg.intermediate_size * L
    assert phases["logits"].flops == 2 * batch * cfg.vocab_size * h
    assert phases["sampling"].flops == 0
    total = cm.total_cost(phases)
    assert total.flops == sum(p.flops for p in phases.values())
    assert total.hbm_bytes == sum(p.hbm_bytes for p in phases.values())


def test_decode_step_int8_kv_moves_fewer_bytes():
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    kw = dict(batch=32, kv_len=160, block_size=16)
    bf16 = cm.total_cost(cm.decode_step_cost(cfg, kv_dtype="bfloat16", **kw))
    int8 = cm.total_cost(cm.decode_step_cost(cfg, kv_dtype="int8", **kw))
    assert int8.flops == bf16.flops
    assert int8.hbm_bytes < bf16.hbm_bytes


def test_decode_step_kv_dtype_bytes_strictly_ordered():
    """bf16 > int8 > int4 step bytes at long context — the lever the int4
    cache pulls — with identical matmul volume across all three."""
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    kw = dict(batch=16, kv_len=8192, block_size=16)
    costs = {kv: cm.total_cost(cm.decode_step_cost(cfg, kv_dtype=kv, **kw))
             for kv in cm.KV_DTYPES}
    assert costs["bfloat16"].flops == costs["int8"].flops == costs["int4"].flops
    assert (costs["bfloat16"].hbm_bytes > costs["int8"].hbm_bytes
            > costs["int4"].hbm_bytes)


def test_predicted_decode_perf_per_kv_dtype_ordering():
    """The roofline prediction must rank int4 > int8 > bf16 tok/s in the
    bandwidth-bound long-context regime (the bench longctx sweep's claim)."""
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    hw = cm.hw_spec_for("tpu v5 lite")
    preds = {kv: cm.predicted_decode_perf(
        cfg, hw, batch=16, kv_len=8192, kv_dtype=kv)["tok_s"]
        for kv in cm.KV_DTYPES}
    assert preds["int4"] > preds["int8"] > preds["bfloat16"] > 0


def test_analytic_param_bytes_matches_runtime():
    """Shape-derived parameter bytes == bytes of actually-initialized
    params (both precisions), so roofline predictions use real weights."""
    import jax

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.quant import param_bytes, quantize_params_int8

    cfg = resolve_model_config("tiny-llama")
    params = llama.init_params(cfg, jax.random.key(0))
    assert cm.analytic_param_bytes(cfg, "none") == param_bytes(params)
    qparams = quantize_params_int8(params, cfg)
    # Quantized: matmul leaves shrink to 1B + f32 scales; the analytic twin
    # ignores the (per-channel, O(h)) scale vectors -> small underestimate.
    analytic = cm.analytic_param_bytes(cfg, "int8")
    actual = param_bytes(qparams)
    assert analytic <= actual < analytic * 1.1


def test_hw_spec_lookup():
    assert cm.hw_spec_for("TPU v5 lite").name == "tpu-v5e"
    assert cm.hw_spec_for("TPU v5p chip").name == "tpu-v5p"
    assert cm.hw_spec_for("TPU v6e").name == "tpu-v6e"
    assert cm.hw_spec_for("cpu").name == "cpu"  # a named entry, for tests
    # A device that is not in the table is an error, not a default: its
    # peaks feed live decisions and every utilization.
    for unknown in ("", "TPU v9", "NVIDIA H100"):
        with pytest.raises(ValueError, match="no hardware spec"):
            cm.hw_spec_for(unknown)


def test_mixed_step_cost_hand_computed_all_kv_dtypes():
    """The unified-step pricing is the hand-computed aggregate of its
    decode rows and the chunk: 3 decode rows at kv_len 10 @ block 4 →
    3 blocks each (12 block-rounded ctx positions); an 8-token chunk at
    kv_len 8 → 2 blocks (8 q × 8 rounded ctx). Holds for every kv cache
    dtype (the dtype only scales the attention HBM side)."""
    cfg = resolve_model_config("tiny-llama")
    bs = 4
    for kv in cm.KV_DTYPES:
        mixed = cm.total_cost(cm.mixed_step_cost(
            cfg, decode_rows=3, decode_kv_len=10, chunk=8, chunk_kv_len=8,
            block_size=bs, kv_dtype=kv))
        twin = cm.total_cost(cm.model_step_cost(
            cfg, tokens=3 + 8, logit_rows=3 + 1,
            attn_q_ctx=float(3 * 3 * bs + 8 * 2 * bs),
            kv_blocks=float(3 * 3 + 2), block_size=bs, kv_dtype=kv))
        assert mixed.flops == twin.flops, kv
        assert mixed.hbm_bytes == twin.hbm_bytes, kv


def test_mixed_step_cost_chunk_zero_is_pure_decode():
    """chunk=0 degenerates to the decode-only step: no extra logit row,
    no prefill attention volume — byte-for-byte the decode_step_cost."""
    cfg = resolve_model_config("tiny-llama")
    pure = cm.total_cost(cm.mixed_step_cost(
        cfg, decode_rows=3, decode_kv_len=10, chunk=0, chunk_kv_len=0,
        block_size=4))
    dec = cm.total_cost(cm.decode_step_cost(
        cfg, batch=3, kv_len=10, block_size=4))
    assert pure.flops == dec.flops
    assert pure.hbm_bytes == dec.hbm_bytes


def test_mixed_step_seconds_monotonic_in_chunk():
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    hw = cm.hw_spec_for("tpu v5 lite")
    kw = dict(decode_rows=16, decode_kv_len=4096, block_size=16)
    s0 = cm.mixed_step_seconds(cfg, hw, chunk=0, chunk_kv_len=0, **kw)
    s256 = cm.mixed_step_seconds(cfg, hw, chunk=256, chunk_kv_len=256, **kw)
    s1024 = cm.mixed_step_seconds(cfg, hw, chunk=1024, chunk_kv_len=1024, **kw)
    assert 0 < s0 < s256 < s1024


def test_auto_prefill_chunk_slo_and_qos_ordering():
    """The SLO-driven chunk is monotone in the ITL budget, follows the
    per-QoS ladder (batch's 4x budget ⇒ chunk ≥ standard ≥ interactive),
    lands on the pow2 ladder, and floors at min_chunk when the SLO is
    already blown (forward progress over stall-free purity)."""
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    hw = cm.hw_spec_for("tpu v5 lite")
    kw = dict(decode_rows=16, decode_kv_len=4096, block_size=16,
              max_chunk=2048)
    tight = cm.auto_prefill_chunk(cfg, hw, itl_slo_s=0.005, **kw)
    loose = cm.auto_prefill_chunk(cfg, hw, itl_slo_s=0.1, **kw)
    assert 16 <= tight <= loose <= 2048
    chunks = {q: cm.auto_prefill_chunk(cfg, hw, itl_slo_s=0.02,
                                       qos_class=q, **kw)
              for q in cm.QOS_ITL_SLO_SCALE}
    assert (chunks["batch"] >= chunks["standard"]
            >= chunks["interactive"] >= 16)
    for c in (tight, loose, *chunks.values()):
        assert c & (c - 1) == 0, "chunk must sit on the pow2 ladder"
    assert cm.auto_prefill_chunk(cfg, hw, itl_slo_s=1e-9, **kw) == 16
    # the chunk that was picked actually fits its budget
    picked = cm.auto_prefill_chunk(cfg, hw, itl_slo_s=0.02, **kw)
    if picked > 16:
        assert cm.mixed_step_seconds(
            cfg, hw, chunk=picked, chunk_kv_len=picked, **{
                k: v for k, v in kw.items() if k != "max_chunk"}) <= 0.02


def test_predicted_decode_perf_bandwidth_bound():
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    pred = cm.predicted_decode_perf(
        cfg, cm.hw_spec_for("tpu v5 lite"), batch=32, kv_len=160)
    assert pred["bound"] == "bandwidth"
    assert pred["tok_s"] > 0
    assert pred["bw_util_at_roofline"] == pytest.approx(1.0)
    assert 0 < pred["mfu_at_roofline"] < 1


# ---------------------------------------------------------------------------
# Phase hooks
# ---------------------------------------------------------------------------

def test_phase_is_named_scope():
    import jax
    assert isinstance(phase("attention"), type(jax.named_scope("x")))


# ---------------------------------------------------------------------------
# Step profiler: engine integration + disabled-mode bound
# ---------------------------------------------------------------------------

def test_engine_step_ring_carries_perf_counters():
    from dynamo_tpu.engine.engine import EngineCore
    from dynamo_tpu.obs.tracer import get_tracer

    core = EngineCore(tiny_config())
    run_to_completion(core, [make_req(), make_req()])
    recs = [r for r in get_tracer().recorder.steps.snapshot()
            if r.flops > 0]
    assert recs, "no step record carried perf counters"
    rec = recs[-1]
    d = rec.to_dict()
    for key in ("decode_tokens", "prefill_tokens", "flops", "hbm_bytes",
                "tok_s", "mfu", "bw_util", "roofline_frac"):
        assert key in d
    assert rec.hbm_bytes > 0 and rec.tok_s > 0
    assert 0 <= rec.mfu <= 1.5  # tiny model on CPU spec: loose sanity bound


def test_profiler_disabled_is_inert(monkeypatch):
    """DYN_PERF_PROFILE=0: measure() returns {} BEFORE any cost-model math
    (the overhead bound) and the engine still steps fine. The scheduling
    ledger prices step geometry through the same cost model behind its own
    independent gate (inertness covered by tests/test_sched_obs.py), so it
    is disabled here too."""
    from dynamo_tpu.obs.sched_ledger import SCHED_ENV, get_sched_ledger

    monkeypatch.setenv("DYN_PERF_PROFILE", "0")
    monkeypatch.setenv(SCHED_ENV, "0")
    cfg = resolve_model_config("tiny-llama")
    prof = StepPerfProfiler(tiny_config_model(), tiny_config(),
                            device_kind="cpu")
    assert prof.enabled is False
    monkeypatch.setattr(cm, "model_step_cost",
                        _raise_if_called, raising=True)
    monkeypatch.setattr(cm, "step_work", _raise_if_called, raising=True)
    assert prof.measure(_counts(
        [(_DECODE, [(0, 5, 1)], [0], _FakeArr((1,)), None)]), 0.01) == {}
    del cfg

    from dynamo_tpu.engine.engine import EngineCore
    core = EngineCore(tiny_config())
    out, fin = run_to_completion(core, [make_req()])
    assert fin  # engine unaffected
    assert core.perf.enabled is False
    get_sched_ledger().configure(True)  # don't leak the gate to other tests


def tiny_config_model():
    return resolve_model_config("tiny-llama")


_DECODE = BucketSig("decode", 8, 1, 4, True, "bfloat16")


class _FakeArr:
    def __init__(self, shape):
        self.shape = shape
        self.ndim = len(shape)


def _counts(batches, windows=(0, 0), dec_rows=0):
    """The one count of a step's rows, as EngineCore._record_step makes it
    for the profiler (block size 16, the tiny preset's two full layers)."""
    from dynamo_tpu.obs.sched_ledger import step_counts

    return step_counts(batches, 16, windows, dec_rows=dec_rows)


def _raise_if_called(*a, **k):
    raise AssertionError("cost model must not run when profiler disabled")


def test_profiler_charges_decode_and_prefill_rows():
    ecfg = tiny_config()
    prof = StepPerfProfiler(tiny_config_model(), ecfg, device_kind="cpu",
                            enabled=True)
    batches = [
        (BucketSig("mixed", 8, 16, 4, True, "bfloat16"), [(0, 0, 8)], [0],
         _FakeArr((1,)), None),
        (_DECODE, [(1, 8, 1), (2, 12, 1)], [0, 1], _FakeArr((2,)), None),
    ]
    counts = _counts(batches)
    # The walk measure() made itself until PR 43, now the step's one count:
    # a chunk of 8 from 0, decode rows at 8 and 12; two full layers.
    assert counts["live_tokens"] == 10 and counts["logit_rows"] == 3
    assert counts["programs"] == 2 and counts["kv_blocks_live"] == 3
    assert counts["kv_blocks_walked"] == 2 * 3
    assert counts["attn_q_ctx"] == 2 * (8 * 9 // 2 + 9 + 13)
    fields = prof.measure(counts, wall_s=0.05)
    assert fields["prefill_tokens"] == 8
    assert fields["decode_tokens"] == 2
    assert fields["flops"] > 0 and fields["hbm_bytes"] > 0
    assert fields["tok_s"] == pytest.approx(2 / 0.05)  # generated tokens/s
    # Priced by the program's shapes from that count, and by nothing else:
    # the ledger's goodput takes the same cost for its live side.
    cost = cm.step_work(prof.shapes, counts)
    assert (fields["flops"], fields["hbm_bytes"]) == (cost.flops,
                                                      cost.hbm_bytes)
    assert prof.last_cost == cost


def test_perf_metrics_family_exposed():
    reg = MetricsRegistry()
    PerfMetrics(reg)
    text = reg.expose()
    for name in ("dynamo_engine_perf_mfu", "dynamo_engine_perf_hbm_bw_util",
                 "dynamo_engine_perf_roofline_fraction",
                 "dynamo_engine_perf_model_flops_total",
                 "dynamo_engine_perf_hbm_bytes_total",
                 "dynamo_engine_perf_step_seconds"):
        assert name in text


def test_perf_tok_s_gauge_labeled_by_kv_dtype():
    """The tokens/s gauge carries kind AND kv_dtype labels (the contract
    declared in tools/lint_metrics.py PERF_METRIC_LABELS)."""
    from dynamo_tpu.obs.profiler import install_perf_metrics

    reg = MetricsRegistry()
    install_perf_metrics(reg)
    prof = StepPerfProfiler(tiny_config_model(), tiny_config(kv_dtype="int4"),
                            device_kind="cpu", enabled=True)
    prof.measure(_counts(
        [(_DECODE, [(0, 8, 1)], [0], _FakeArr((1,)), None)]), 0.01)
    text = reg.expose()
    assert 'kv_dtype="int4"' in text and 'kind="decode"' in text


def test_lint_flags_perf_label_drift(tmp_path):
    """A tok_s emit whose labels drift from PERF_METRIC_LABELS fails the
    metrics lint (the dashboard PromQL contract)."""
    import textwrap

    from tools.lint_metrics import lint_tree

    obs = tmp_path / "obs"
    obs.mkdir()
    (obs / "profiler.py").write_text(textwrap.dedent("""
        class P:
            def bind(self, registry):
                self.tok_s = registry.gauge(
                    "engine_perf_tokens_per_second", "help")
            def measure(self):
                self.tok_s.set(1.0, kind="decode")  # kv_dtype missing
    """))
    problems = lint_tree(tmp_path)
    assert any("PERF_METRIC_LABELS" in p and "kv_dtype" in p
               for p in problems), "\n".join(problems)


def test_costmodel_ring_vs_chunked_crossover_and_break_even():
    """Ring prefill loses on one-block prompts (ICI hops dominate a
    single chunk), wins on long ones (chunked-sequential re-reads the
    growing KV, ring shards it sp ways); the bisected break-even sits
    between those two probes, the decision flips exactly there, and sp=1
    never engages (the probe returns its max_tokens cap)."""
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    hw = cm.hw_spec_for("tpu v5 lite")
    kw = dict(sp=8, chunk=512, block_size=16)
    short = cm.ring_vs_chunked_prefill(cfg, hw, prompt_tokens=16, **kw)
    long = cm.ring_vs_chunked_prefill(cfg, hw, prompt_tokens=131072, **kw)
    assert not short.use_ring and long.use_ring
    assert long.speedup > 1.0
    be = cm.ring_prefill_break_even_tokens(cfg, hw, **kw)
    assert 16 < be <= 131072 and be % 16 == 0
    assert cm.ring_vs_chunked_prefill(cfg, hw, prompt_tokens=be, **kw).use_ring
    assert cm.ring_prefill_break_even_tokens(
        cfg, hw, sp=1, chunk=512, block_size=16) == 1 << 20


def test_costmodel_session_retention_cost_scales_with_kv_dtype():
    """Retention pricing: quantized KV shrinks bytes/token (cheaper to
    hold a session) while recompute seconds are dtype-independent, so
    seconds_per_gb — the knob operators tune TTL against — rises."""
    cfg = MODEL_PRESETS["llama-3-8b-lite"]
    hw = cm.hw_spec_for("tpu v5 lite")
    kw = dict(block_size=16, quantization="none")
    bf16 = cm.session_retention_cost(cfg, hw, kv_dtype="bfloat16", **kw)
    int8 = cm.session_retention_cost(cfg, hw, kv_dtype="int8", **kw)
    assert bf16.bytes_per_token > int8.bytes_per_token > 0
    assert bf16.seconds_per_token == int8.seconds_per_token > 0
    assert int8.seconds_per_gb > bf16.seconds_per_gb > 0
    tokens = 4096
    assert bf16.retained_bytes(tokens) == bf16.bytes_per_token * tokens
    assert bf16.recompute_seconds(tokens) == pytest.approx(
        bf16.seconds_per_token * tokens)
