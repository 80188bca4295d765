"""Per-process system status server: /health, /live, /metrics.

Fills the role of the reference's system status server
(reference: lib/runtime/src/system_status_server.rs:1-811 + system_health.rs
— an env-gated (DYN_SYSTEM_ENABLED / DYN_SYSTEM_PORT) HTTP endpoint every
process can expose, independent of any model-serving frontend, giving
k8s probes and Prometheus a uniform per-process surface).

Workers previously published metrics only over the coordinator; with this,
every DistributedRuntime process can also be scraped/probed directly.
Status providers (e.g. the worker's engine stats fn) plug in at runtime.
"""

from __future__ import annotations

import time
from typing import Callable

from aiohttp import web

from dynamo_tpu.utils.logging import get_logger
from dynamo_tpu.utils.metrics import MetricsRegistry

log = get_logger("status")


class SystemStatusServer:
    def __init__(self, metrics: MetricsRegistry, port: int = 0):
        self.metrics = metrics
        self.port = port
        self._providers: dict[str, Callable[[], dict]] = {}
        self._t0 = time.monotonic()
        self._runner: web.AppRunner | None = None
        # Readiness: a static flag AND an optional dynamic probe (e.g. the
        # worker's health-canary state); /health is 503 when either is off.
        self.ready = True
        self._ready_fn: Callable[[], bool] | None = None

    def set_ready_fn(self, fn: Callable[[], bool]) -> None:
        self._ready_fn = fn

    def _is_ready(self) -> bool:
        try:
            dynamic = self._ready_fn() if self._ready_fn is not None else True
        except Exception:
            dynamic = False
        return self.ready and dynamic

    def add_provider(self, name: str, fn: Callable[[], dict]) -> None:
        """Register a status section (e.g. the engine's stats fn)."""
        self._providers[name] = fn

    async def start(self, host: str = "0.0.0.0") -> int:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/debug/sched", self._debug_sched)
        app.router.add_get("/debug/mem", self._debug_mem)
        app.router.add_get("/debug/phases", self._debug_phases)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        log.info("system status server on port %d", self.port)
        return self.port

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    async def _health(self, request: web.Request) -> web.Response:
        ready = self._is_ready()
        body = {
            "status": "ready" if ready else "notready",
            "uptime_s": round(time.monotonic() - self._t0, 1),
        }
        for name, fn in self._providers.items():
            try:
                body[name] = fn()
            except Exception as exc:  # noqa: BLE001 - a broken provider
                body[name] = {"error": str(exc)}  # must not break the probe
        return web.json_response(body, status=200 if ready else 503)

    async def _live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _debug_sched(self, request: web.Request) -> web.Response:
        """Worker-local scheduling ledger (obs/sched_ledger.py): the
        recent-step ring, goodput trend, and top HOL culprits of THIS
        process's engine — span-level victim detail lives in the worker's
        own FlightRecorder, so merge it in."""
        from dynamo_tpu.obs.sched_ledger import get_sched_ledger
        from dynamo_tpu.obs.tracer import get_tracer

        return web.json_response(get_sched_ledger().debug_info(
            recorder=get_tracer().recorder))

    async def _debug_mem(self, request: web.Request) -> web.Response:
        """Worker-local memory ledger (obs/mem_ledger.py): the tier
        occupancy waterfall, top pin owners, churn trend, consumption
        rates, TTX forecast, and the last pin-leak audit report."""
        from dynamo_tpu.obs.mem_ledger import get_mem_ledger

        return web.json_response(get_mem_ledger().debug_info())

    async def _debug_phases(self, request: web.Request) -> web.Response:
        """``{program: {instruction: phase}}`` of the step programs this
        process's engine has built (obs/profiler.py ``debug_phases``): each
        is lowered again and its compiled text read (jit holds both: tens of
        milliseconds a program), in a thread of its own; ``{}`` where no engine serves here."""
        import asyncio

        from dynamo_tpu.obs.profiler import debug_phases

        return web.json_response(await asyncio.get_running_loop()
                                 .run_in_executor(None, debug_phases))

    async def _metrics(self, request: web.Request) -> web.Response:
        text = self.metrics.expose()
        # Status-provider numeric leaves export as gauges too, so engine
        # stats (kv_usage, num_running, ...) are scrapeable without the
        # coordinator in the path.
        lines = [text] if text else []
        for name, fn in self._providers.items():
            try:
                stats = fn()
            except Exception:
                continue
            for k, v in stats.items():
                if isinstance(v, bool):
                    v = int(v)
                if isinstance(v, (int, float)):
                    lines.append(f"dynamo_{name}_{k} {v}")
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")
