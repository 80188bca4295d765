#!/usr/bin/env python
"""Static lint for Prometheus metric registrations.

Walks the ``dynamo_tpu`` tree with ``ast`` and checks every
``.counter(...)`` / ``.gauge(...)`` / ``.histogram(...)`` /
``.func_gauge(...)`` call (including simple in-module aliases like
``h = registry.histogram``):

* the metric name must be a string constant matching
  ``^[a-z][a-z0-9_]*$`` — the registry prepends ``dynamo_``, so the
  exposed name stays ``dynamo_[a-z0-9_]+`` (Prometheus-valid and
  grep-stable for dashboards);
* the help text must be a non-empty string constant (``help_`` is the
  2nd positional for counter/gauge/histogram, 3rd for func_gauge, or
  the ``help_`` keyword).

Run as a CLI (``python tools/lint_metrics.py [root]``) or from tests via
``lint_tree()``. Exit status 1 and one line per violation on failure.
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

METHODS = {"counter": 1, "gauge": 1, "histogram": 1, "func_gauge": 2}
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

# Status-provider exports: runtime/status.py exposes every numeric leaf of a
# provider's snapshot dict as ``dynamo_<provider>_<key>`` — names that never
# pass through .counter()/.gauge() and so are invisible to the AST walk
# above. This is the declared surface (the engine's EngineMetrics.snapshot
# keys); the same naming rule applies so dashboards can grep one prefix.
PROVIDER_METRICS = {
    "engine": (
        "kv_cache_bytes", "kv_quant_enabled",
        # A list (the per-device cache shape): in stats(), not a gauge.
        "kv_cache_shape",
        # The pool as sized (ModelRunner._fit_pool): blocks, a block's bytes
        # on a device, and the bytes a step holds for each block beyond the
        # pool (None, so no gauge, where nothing was probed).
        "kv_pool_blocks", "kv_block_bytes", "kv_step_copy_bytes_per_block",
        # Time to first token in parts, summed over sequences (engine.py
        # EngineMetrics): means are deltas over deltas of ttft_count.
        "ttft_count", "ttft_inbox_s", "ttft_queue_s", "ttft_prefill_s",
        "num_waiting", "num_running", "kv_usage", "kv_total_blocks",
        "num_steps", "prefill_tokens", "decode_tokens",
        # Host-to-device placements of step inputs (ModelRunner.dispatch):
        # over num_steps, one a greedy step since the inputs are packed.
        "placed_inputs",
        "requests_finished", "preemptions", "prefix_hit_rate",
        "spec_proposed", "spec_accepted", "deadline_cancelled",
        "session_remote_resumes", "stream_ckpt_resumes",
    ),
}

# The streamed KV handoff family (disagg/metrics.py KvTransferMetrics):
# declared here so dashboards have a grep-stable contract and drift in
# either direction — a registration added without declaring it, or a
# declared name that no longer exists — fails the lint.
KV_TRANSFER_METRICS = (
    "kv_transfer_overlap_ratio",
    "kv_transfer_waves_total",
    "kv_transfer_bytes_total",
    "kv_transfer_wave_bytes",
)

# The engine performance-counter family (obs/profiler.py PerfMetrics):
# MFU / HBM-bandwidth / roofline gauges plus cumulative FLOPs-and-bytes
# counters. Same bidirectional drift rule as KV_TRANSFER_METRICS.
PERF_METRICS = (
    "engine_perf_tokens_per_second",
    "engine_perf_mfu",
    "engine_perf_hbm_bw_util",
    "engine_perf_roofline_fraction",
    "engine_perf_model_flops_total",
    "engine_perf_hbm_bytes_total",
    "engine_perf_step_seconds",
    # Seconds per phase of the engine thread's loop (LoopClock.publish).
    "engine_loop_seconds_total",
)

# Label sets of the perf family's labelled series — the dashboard-facing
# contract for every ``.set(...)``/``.inc(...)``/``.observe(...)`` keyword
# in obs/profiler.py. A labelled emit whose metric isn't declared here, or
# whose label names drift from the declared tuple, fails the lint (changing
# a label silently breaks every PromQL ``by (label)`` aggregation).
PERF_METRIC_LABELS = {
    "engine_perf_tokens_per_second": ("kind", "kv_dtype"),
    "engine_loop_seconds_total": ("phase",),
}

# The fleet-wide prefix cache family (kvbm/metrics.py PrefixCacheMetrics):
# onboard outcomes + route-vs-pull arbiter decisions. Same bidirectional
# drift rule as KV_TRANSFER_METRICS.
PREFIX_CACHE_METRICS = (
    "prefix_cache_lookups",
    "prefix_cache_hits",
    "prefix_cache_imported_blocks",
    "prefix_cache_recompute_avoided_tokens",
    "prefix_cache_import_seconds",
    "prefix_cache_published_blocks",
    "prefix_cache_route_decisions",
)

# The session KV-retention family (engine/session.py SessionMetrics):
# per-turn reuse counters plus live retained-state gauges. Same
# bidirectional drift rule as KV_TRANSFER_METRICS.
SESSION_METRICS = (
    "session_lookups",
    "session_hits",
    "session_avoided_tokens",
    "session_retained_blocks",
    "session_active",
    "session_expired",
    "session_demoted_blocks",
    "session_remote_resumes",
)

# The worker drain family (runtime/drain.py DrainMetrics): run-down
# progress, evacuation volume, and the operator-abort counter. Same
# bidirectional drift rule as KV_TRANSFER_METRICS.
DRAIN_METRICS = (
    "drain_duration_seconds",
    "drain_streams_completed",
    "drain_streams_aborted",
    "drain_evacuated_blocks",
    "drain_evacuated_bytes",
    "drain_evacuated_sessions",
    "drain_active",
    "drain_aborted",
)

# The planner process-connector family (planner/connector.py
# ConnectorMetrics): replica lifecycle counts plus the drain-to-exit
# latency histogram. Same bidirectional drift rule as KV_TRANSFER_METRICS.
CONNECTOR_METRICS = (
    "connector_replicas_spawned",
    "connector_replicas_retired",
    "connector_sigkill_escalations",
    "connector_drain_seconds",
)

# The context-parallel ring prefill family (obs/ring_prefill.py
# RingPrefillMetrics): engage/bypass counters plus the live auto-threshold
# gauge. Same bidirectional drift rule as KV_TRANSFER_METRICS.
RING_PREFILL_METRICS = (
    "ring_prefill_invocations",
    "ring_prefill_tokens",
    "ring_prefill_bypassed",
    "ring_prefill_threshold_tokens",
)

# The XLA compile-ledger family (obs/compile_ledger.py CompileMetrics):
# compile events/walls, live compiled-program inventory, serve-path stall
# accounting, and warmup lattice coverage. Same bidirectional drift rule
# as KV_TRANSFER_METRICS.
COMPILE_METRICS = (
    "xla_compile_events_total",
    "xla_compile_seconds",
    "xla_compile_cache_entries",
    "xla_compile_inflight",
    "xla_compile_stall_seconds_total",
    "xla_compile_warmup_coverage",
    "xla_compile_warmup_buckets",
)

# The recurrent layers' step counts (obs/sched_ledger.py SSM_COUNTS), each a
# ``<name>_total`` leaf of the engine provider's ``sched`` section: what the
# mixers computed, and of the one-token update's kernel the rows its grid
# was given and the rows whose state it moved. Same bidirectional drift rule.
SCHED_SSM_COUNTS = (
    "ssm_layer_steps",
    "ssm_live_tokens",
    "ssm_scanned_positions",
    "ssm_state_rows",
    "ssm_update_rows_given",
    "ssm_update_rows_moved",
    "ssm_scan_rows",
    "ssm_scan_positions",
)

# The scheduling-ledger family (obs/sched_ledger.py SchedMetrics):
# per-step goodput/padding-waste gauges, admission/preemption cause
# counters, and the HOL-stall histogram. Same bidirectional drift rule
# as KV_TRANSFER_METRICS.
SCHED_METRICS = (
    "sched_goodput_fraction",
    "sched_token_budget_utilization",
    "sched_queue_depth",
    "sched_steps_total",
    "sched_admission_blocked_total",
    "sched_preempt_recompute_tokens_total",
    "sched_padding_flops_total",
    "sched_padding_hbm_bytes_total",
    "sched_hol_stall_seconds",
    "sched_interference_row_seconds_total",
    "sched_prefill_chunk_tokens",
)

# The fleet-aggregation family (obs/fleet.py FleetAggregator): scrape
# attempts/failures, target freshness, and sweep latency. Same
# bidirectional drift rule as KV_TRANSFER_METRICS.
FLEET_METRICS = (
    "fleet_scrapes_total",
    "fleet_scrape_errors_total",
    "fleet_targets",
    "fleet_scrape_seconds",
    "fleet_compile_storm",
)

# The SLO burn-rate family (obs/fleet.py SloEngine): error-budget gauges
# plus the rising-edge violation counter. Same bidirectional drift rule
# as KV_TRANSFER_METRICS (both families register in obs/fleet.py, so one
# check covers FLEET_METRICS + SLO_METRICS together).
SLO_METRICS = (
    "slo_error_budget_remaining",
    "slo_burn_rate",
    "slo_violations_total",
)

# The crash-consistent stream-checkpoint family (kvbm/stream_ckpt.py
# StreamCkptMetrics): checkpoint write volume, resume outcomes, and the
# lag/TTL health gauges. Same bidirectional drift rule as
# KV_TRANSFER_METRICS.
STREAM_CKPT_METRICS = (
    "stream_ckpt_writes",
    "stream_ckpt_bytes",
    "stream_ckpt_resumes",
    "stream_ckpt_resume_recomputed_tokens",
    "stream_ckpt_lag_blocks",
    "stream_ckpt_expired",
)

# The KV memory & capacity ledger family (obs/mem_ledger.py MemMetrics):
# per-owner device occupancy, tier waterfall, churn/alloc/release counters,
# the pin-leak audit gauges, and the TTX forecast pair. Same bidirectional
# drift rule as KV_TRANSFER_METRICS.
MEM_METRICS = (
    "mem_device_blocks",
    "mem_tier_blocks",
    "mem_tier_bytes",
    "mem_churn_blocks_total",
    "mem_orphan_pins",
    "mem_audits_total",
    "mem_ttx_seconds",
    "mem_capacity_posture",
    "mem_alloc_blocks_total",
    "mem_release_blocks_total",
    "mem_headroom_observations_total",
)

# The failure-recovery family: health canaries (runtime/health.py),
# migration re-dispatch (frontend/migration.py), and chaos injection
# (chaos/metrics.py). Same bidirectional drift rule as KV_TRANSFER_METRICS:
# each module's registrations must exactly match its declared slice.
RECOVERY_METRICS = {
    ("runtime", "health.py"): ("health_canary_total", "health_canary_failures"),
    ("frontend", "migration.py"): ("migration_attempts_total",),
    ("chaos", "metrics.py"): ("chaos_injected_total",),
}


def _const_str(node: ast.expr | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _check_call(call: ast.Call, method: str, path: Path,
                problems: list[str]) -> None:
    where = f"{path}:{call.lineno}"
    help_idx = METHODS[method]

    name = _const_str(call.args[0]) if call.args else None
    if call.args and name is None:
        # Dynamic names defeat static dashboards/grep; flag them.
        problems.append(f"{where}: {method}() name is not a string constant")
        return
    if name is None:
        problems.append(f"{where}: {method}() called without a metric name")
        return
    if not NAME_RE.match(name):
        problems.append(
            f"{where}: metric name {name!r} does not match "
            f"[a-z][a-z0-9_]* (exposed as dynamo_<name>)")

    help_node: ast.expr | None = None
    for kw in call.keywords:
        if kw.arg == "help_":
            help_node = kw.value
    if help_node is None and len(call.args) > help_idx:
        help_node = call.args[help_idx]
    help_text = _const_str(help_node)
    if help_node is None or help_text is None or not help_text.strip():
        problems.append(
            f"{where}: metric {name!r} needs non-empty constant help text")


def _lint_module(path: Path, problems: list[str]) -> None:
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as exc:  # a broken module is its own violation
        problems.append(f"{path}: syntax error: {exc}")
        return

    # First pass: in-module aliases of registration methods
    # (e.g. ``h = registry.histogram`` in obs/bridge.py).
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Attribute)
                and node.value.attr in METHODS):
            aliases[node.targets[0].id] = node.value.attr

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in METHODS:
            _check_call(node, fn.attr, path, problems)
        elif isinstance(fn, ast.Name) and fn.id in aliases:
            _check_call(node, aliases[fn.id], path, problems)


def _snapshot_keys(path: Path) -> set[str] | None:
    """Constant keys of EngineMetrics.snapshot's returned dict (None if the
    module/shape isn't found — e.g. linting a partial tree in tests)."""
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (OSError, SyntaxError):
        return None
    for cls in ast.walk(tree):
        if not (isinstance(cls, ast.ClassDef) and cls.name == "EngineMetrics"):
            continue
        for fn in cls.body:
            if not (isinstance(fn, ast.FunctionDef) and fn.name == "snapshot"):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) and isinstance(node.value, ast.Dict):
                    return {k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)
                            and isinstance(k.value, str)}
    return None


def _registered_names(path: Path) -> set[str] | None:
    """Constant metric names registered via .counter()/.gauge()/... calls in
    one module (None if the module isn't found — partial trees in tests)."""
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (OSError, SyntaxError):
        return None
    names: set[str] = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in METHODS and node.args):
            name = _const_str(node.args[0])
            if name is not None:
                names.add(name)
    return names


def _lint_kv_transfer_metrics(root: Path, problems: list[str]) -> None:
    """The streamed-handoff family must match what disagg/metrics.py
    actually registers — same no-silent-drift rule as PROVIDER_METRICS."""
    actual = _registered_names(root / "disagg" / "metrics.py")
    if actual is None:
        return
    declared = set(KV_TRANSFER_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"disagg/metrics.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py KV_TRANSFER_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"KV_TRANSFER_METRICS declares {key!r} but disagg/metrics.py "
            "does not register it")


def _lint_prefix_cache_metrics(root: Path, problems: list[str]) -> None:
    """The prefix-cache family must match what kvbm/metrics.py actually
    registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "kvbm" / "metrics.py")
    if actual is None:
        return
    declared = set(PREFIX_CACHE_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"kvbm/metrics.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py PREFIX_CACHE_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"PREFIX_CACHE_METRICS declares {key!r} but kvbm/metrics.py "
            "does not register it")


def _lint_perf_metrics(root: Path, problems: list[str]) -> None:
    """The dynamo_engine_perf_* family must match what obs/profiler.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "obs" / "profiler.py")
    if actual is None:
        return
    declared = set(PERF_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/profiler.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py PERF_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"PERF_METRICS declares {key!r} but obs/profiler.py "
            "does not register it")


def _lint_perf_labels(root: Path, problems: list[str]) -> None:
    """Labelled emits in obs/profiler.py must carry exactly the label names
    PERF_METRIC_LABELS declares for their metric (and any newly-labelled
    metric must be declared). The attr→metric-name map comes from the
    ``self.<attr> = registry.gauge("<name>", ...)`` assignments in
    PerfMetrics.bind, so the check follows renames automatically."""
    path = root / "obs" / "profiler.py"
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (OSError, SyntaxError):
        return
    attr_to_metric: dict[str, str] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Attribute)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and node.value.func.attr in METHODS and node.value.args):
            name = _const_str(node.value.args[0])
            if name is not None:
                attr_to_metric[node.targets[0].attr] = name
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("set", "inc", "observe")
                and isinstance(node.func.value, ast.Attribute)):
            continue
        metric = attr_to_metric.get(node.func.value.attr)
        if metric is None:
            continue
        labels = tuple(sorted(
            kw.arg for kw in node.keywords if kw.arg is not None))
        declared = PERF_METRIC_LABELS.get(metric)
        where = f"{path}:{node.lineno}"
        if declared is None:
            if labels:
                problems.append(
                    f"{where}: {metric!r} emitted with labels {labels} but "
                    "has no entry in tools/lint_metrics.py "
                    "PERF_METRIC_LABELS")
        elif labels != tuple(sorted(declared)):
            problems.append(
                f"{where}: {metric!r} emitted with labels {labels}, "
                f"PERF_METRIC_LABELS declares {tuple(sorted(declared))}")


def _lint_session_metrics(root: Path, problems: list[str]) -> None:
    """The session-retention family must match what engine/session.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "engine" / "session.py")
    if actual is None:
        return
    declared = set(SESSION_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"engine/session.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py SESSION_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"SESSION_METRICS declares {key!r} but engine/session.py "
            "does not register it")


def _lint_drain_metrics(root: Path, problems: list[str]) -> None:
    """The worker-drain family must match what runtime/drain.py actually
    registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "runtime" / "drain.py")
    if actual is None:
        return
    declared = set(DRAIN_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"runtime/drain.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py DRAIN_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"DRAIN_METRICS declares {key!r} but runtime/drain.py "
            "does not register it")


def _lint_connector_metrics(root: Path, problems: list[str]) -> None:
    """The process-connector family must match what planner/connector.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "planner" / "connector.py")
    if actual is None:
        return
    declared = set(CONNECTOR_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"planner/connector.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py CONNECTOR_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"CONNECTOR_METRICS declares {key!r} but planner/connector.py "
            "does not register it")


def _lint_ring_prefill_metrics(root: Path, problems: list[str]) -> None:
    """The ring-prefill family must match what obs/ring_prefill.py actually
    registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "obs" / "ring_prefill.py")
    if actual is None:
        return
    declared = set(RING_PREFILL_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/ring_prefill.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py RING_PREFILL_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"RING_PREFILL_METRICS declares {key!r} but obs/ring_prefill.py "
            "does not register it")


def _lint_compile_metrics(root: Path, problems: list[str]) -> None:
    """The compile-ledger family must match what obs/compile_ledger.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "obs" / "compile_ledger.py")
    if actual is None:
        return
    declared = set(COMPILE_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/compile_ledger.py registers {key!r} but it is missing "
            "from tools/lint_metrics.py COMPILE_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"COMPILE_METRICS declares {key!r} but obs/compile_ledger.py "
            "does not register it")


def _lint_sched_metrics(root: Path, problems: list[str]) -> None:
    """The scheduling-ledger family must match what obs/sched_ledger.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "obs" / "sched_ledger.py")
    if actual is None:
        return
    declared = set(SCHED_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/sched_ledger.py registers {key!r} but it is missing "
            "from tools/lint_metrics.py SCHED_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"SCHED_METRICS declares {key!r} but obs/sched_ledger.py "
            "does not register it")


def _module_tuple(path: Path, name: str) -> set[str] | None:
    """The string constants of the module-level tuple ``name`` (None if the
    module or the assignment isn't found — partial trees in tests)."""
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (OSError, SyntaxError):
        return None
    for node in tree.body:
        if (isinstance(node, ast.Assign) and isinstance(node.value, ast.Tuple)
                and any(isinstance(t, ast.Name) and t.id == name
                        for t in node.targets)):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)}
    return None


def _lint_sched_ssm_counts(root: Path, problems: list[str]) -> None:
    """SCHED_SSM_COUNTS must match obs/sched_ledger.py SSM_COUNTS, whose
    names the ledger's snapshot exports with ``_total`` behind them."""
    actual = _module_tuple(root / "obs" / "sched_ledger.py", "SSM_COUNTS")
    if actual is None:
        return
    declared = set(SCHED_SSM_COUNTS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/sched_ledger.py SSM_COUNTS has {key!r} but it is missing "
            "from tools/lint_metrics.py SCHED_SSM_COUNTS")
    for key in sorted(declared - actual):
        problems.append(
            f"SCHED_SSM_COUNTS declares {key!r} but obs/sched_ledger.py "
            "SSM_COUNTS does not have it")
    for key in sorted(declared):
        if not NAME_RE.match(f"engine_sched_{key}_total"):
            problems.append(
                f"SCHED_SSM_COUNTS: {key} does not match [a-z][a-z0-9_]*")


def _lint_fleet_metrics(root: Path, problems: list[str]) -> None:
    """FLEET_METRICS + SLO_METRICS together must match what obs/fleet.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS.
    A name in the wrong family is caught by the prefix rule: the fleet
    family is fleet_*, the SLO family slo_*."""
    actual = _registered_names(root / "obs" / "fleet.py")
    if actual is None:
        return
    for key in SLO_METRICS:
        if not key.startswith("slo_"):
            problems.append(
                f"SLO_METRICS declares {key!r} which is not slo_*-prefixed")
    for key in FLEET_METRICS:
        if not key.startswith("fleet_"):
            problems.append(
                f"FLEET_METRICS declares {key!r} which is not "
                "fleet_*-prefixed")
    declared = set(FLEET_METRICS) | set(SLO_METRICS)
    for key in sorted(actual - declared):
        family = "SLO_METRICS" if key.startswith("slo_") else "FLEET_METRICS"
        problems.append(
            f"obs/fleet.py registers {key!r} but it is missing from "
            f"tools/lint_metrics.py {family}")
    for key in sorted(declared - actual):
        problems.append(
            f"FLEET_METRICS/SLO_METRICS declare {key!r} but obs/fleet.py "
            "does not register it")


def _lint_stream_ckpt_metrics(root: Path, problems: list[str]) -> None:
    """The stream-checkpoint family must match what kvbm/stream_ckpt.py
    actually registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "kvbm" / "stream_ckpt.py")
    if actual is None:
        return
    declared = set(STREAM_CKPT_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"kvbm/stream_ckpt.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py STREAM_CKPT_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"STREAM_CKPT_METRICS declares {key!r} but kvbm/stream_ckpt.py "
            "does not register it")


def _lint_mem_metrics(root: Path, problems: list[str]) -> None:
    """The memory-ledger family must match what obs/mem_ledger.py actually
    registers — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    actual = _registered_names(root / "obs" / "mem_ledger.py")
    if actual is None:
        return
    declared = set(MEM_METRICS)
    for key in sorted(actual - declared):
        problems.append(
            f"obs/mem_ledger.py registers {key!r} but it is missing from "
            "tools/lint_metrics.py MEM_METRICS")
    for key in sorted(declared - actual):
        problems.append(
            f"MEM_METRICS declares {key!r} but obs/mem_ledger.py "
            "does not register it")


def _lint_family_overlap(problems: list[str]) -> None:
    """No metric name may appear in two declared families: a duplicate
    means two modules would register (or two dashboards would grep) the
    same dynamo_<name> series with different meanings."""
    families: dict[str, tuple[str, ...]] = {
        "KV_TRANSFER_METRICS": KV_TRANSFER_METRICS,
        "PERF_METRICS": PERF_METRICS,
        "PREFIX_CACHE_METRICS": PREFIX_CACHE_METRICS,
        "SESSION_METRICS": SESSION_METRICS,
        "DRAIN_METRICS": DRAIN_METRICS,
        "CONNECTOR_METRICS": CONNECTOR_METRICS,
        "RING_PREFILL_METRICS": RING_PREFILL_METRICS,
        "COMPILE_METRICS": COMPILE_METRICS,
        "SCHED_METRICS": SCHED_METRICS,
        "STREAM_CKPT_METRICS": STREAM_CKPT_METRICS,
        "MEM_METRICS": MEM_METRICS,
        "FLEET_METRICS": FLEET_METRICS,
        "SLO_METRICS": SLO_METRICS,
        **{f"RECOVERY_METRICS[{'/'.join(parts)}]": names
           for parts, names in RECOVERY_METRICS.items()},
    }
    seen: dict[str, str] = {}
    for family, names in families.items():
        for name in names:
            if name in seen:
                problems.append(
                    f"metric {name!r} declared in both {seen[name]} and "
                    f"{family} — families must not overlap")
            else:
                seen[name] = family


def _lint_recovery_metrics(root: Path, problems: list[str]) -> None:
    """The recovery family must match what each module actually registers
    — same no-silent-drift rule as KV_TRANSFER_METRICS."""
    for parts, declared_names in RECOVERY_METRICS.items():
        rel = "/".join(parts)
        actual = _registered_names(root.joinpath(*parts))
        if actual is None:
            continue
        declared = set(declared_names)
        for key in sorted(actual - declared):
            problems.append(
                f"{rel} registers {key!r} but it is missing from "
                "tools/lint_metrics.py RECOVERY_METRICS")
        for key in sorted(declared - actual):
            problems.append(
                f"RECOVERY_METRICS declares {key!r} but {rel} "
                "does not register it")


def _lint_provider_metrics(root: Path, problems: list[str]) -> None:
    """The status-provider surface: names must be Prometheus-valid under the
    dynamo_ prefix, and the declared engine list must match what
    EngineMetrics.snapshot actually returns (no silent drift either way)."""
    for provider, keys in PROVIDER_METRICS.items():
        for key in keys:
            if not NAME_RE.match(f"{provider}_{key}"):
                problems.append(
                    f"PROVIDER_METRICS: {provider}/{key} does not match "
                    f"[a-z][a-z0-9_]* (exposed as dynamo_{provider}_{key})")
    actual = _snapshot_keys(root / "engine" / "engine.py")
    if actual is None:
        return
    declared = set(PROVIDER_METRICS.get("engine", ()))
    for key in sorted(actual - declared):
        problems.append(
            f"EngineMetrics.snapshot exports {key!r} but it is missing from "
            "tools/lint_metrics.py PROVIDER_METRICS['engine']")
    for key in sorted(declared - actual):
        problems.append(
            f"PROVIDER_METRICS['engine'] declares {key!r} but "
            "EngineMetrics.snapshot does not export it")


def lint_tree(root: Path | None = None) -> list[str]:
    """Lint every ``dynamo_tpu`` module under ``root``; return problems."""
    if root is None:
        root = Path(__file__).resolve().parent.parent / "dynamo_tpu"
    problems: list[str] = []
    for path in sorted(root.rglob("*.py")):
        if "tests" in path.parts:
            continue
        _lint_module(path, problems)
    _lint_provider_metrics(root, problems)
    _lint_kv_transfer_metrics(root, problems)
    _lint_prefix_cache_metrics(root, problems)
    _lint_perf_metrics(root, problems)
    _lint_perf_labels(root, problems)
    _lint_session_metrics(root, problems)
    _lint_drain_metrics(root, problems)
    _lint_connector_metrics(root, problems)
    _lint_ring_prefill_metrics(root, problems)
    _lint_compile_metrics(root, problems)
    _lint_sched_metrics(root, problems)
    _lint_sched_ssm_counts(root, problems)
    _lint_stream_ckpt_metrics(root, problems)
    _lint_mem_metrics(root, problems)
    _lint_fleet_metrics(root, problems)
    _lint_recovery_metrics(root, problems)
    _lint_family_overlap(problems)
    return problems


def main(argv: list[str]) -> int:
    root = Path(argv[1]) if len(argv) > 1 else None
    problems = lint_tree(root)
    for p in problems:
        print(p)
    if problems:
        print(f"{len(problems)} metric lint violation(s)", file=sys.stderr)
        return 1
    print("metrics lint: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
