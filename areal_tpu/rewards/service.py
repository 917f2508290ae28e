"""Sandboxed reward service — HTTP grading core of the reward worker.

Parity target: the reference's standalone functioncall service (the 3k-LoC
deployment behind ``FUNCTIONCALL_SERVICE_DOMAIN``; SURVEY §2.13): a fleet
of sandbox workers that grade math/code tasks over HTTP so untrusted model
code never executes inside the process that drives generation or training.

This module is the jax-free grading core: an aiohttp application exposing

  POST /math_verify    {generated, solutions}            -> {score, verdict}
  POST /code_verify    {generated, input_output, ...}    -> {score, verdict}
  POST /batch_reward   {tasks: [...]}                    -> {scores, verdicts}
  GET  /health                                           liveness + load
  GET  /metrics[.json]                                   Prometheus / JSON

Grading runs on a bounded thread pool; every code grade additionally runs
inside rewards/code_verify.py's rlimit-guarded subprocess (the sandbox
proper), and per-task ``language`` dispatch goes through its GRADERS
registry. A grade that overruns ``grade_timeout_secs`` returns a 0.0 score
with verdict="timeout" and bumps ``reward_timeouts_total`` — the worker
thread is abandoned to finish on its own (the code sandbox enforces its
own rlimits underneath, so an abandoned slot cannot spin forever).

The process-level worker wrapping this core (discovery, supervision,
WorkerControl) is system/reward_worker.py — the sixth worker kind.
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import json
import time
from typing import Any, Dict, List, Optional

from areal_tpu.base import logging, telemetry
from areal_tpu.rewards import code_verify, math_verify

logger = logging.getLogger("rewards.service")

# Verdict vocabulary exported per task kind through telemetry
# (reward_verdicts_total{task=...,verdict=...}).
VERDICTS = ("pass", "fail", "timeout", "error", "unsupported_language")

_LATENCY_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

# Worst-case sampled test cases per code grade — the code-task
# wall-budget floor derives from the grader's own cap.
_CODE_MAX_CASES = code_verify.MAX_CASES_DEFAULT


def task_budget_secs(task: Dict[str, Any], base_secs: float) -> float:
    """Wall budget for ONE task, shared by the service's grade timeout
    and the client's per-task HTTP timeout (rewards/client.py) so the
    two can never disagree: ``base_secs`` bounds a WEDGED grader, while
    a code task floors at its legal worst case (per-case timeout x the
    cases it actually carries, capped at the grader's sample bound,
    + slack) — otherwise correct-but-slow programs get spuriously
    abandoned/zero-scored. Scaling by the real case count matters for
    the pass-rate path's single-case tasks: a hung one-case grade must
    pin its slot ~13s, not ~133s."""
    budget = float(base_secs)
    if task.get("task", "math") == "code":
        n_cases = _CODE_MAX_CASES
        io = task.get("input_output")
        try:
            d = json.loads(io) if isinstance(io, str) else io
            n = len(d.get("inputs", []))
            if n:
                n_cases = min(n, _CODE_MAX_CASES)
        except Exception:  # noqa: BLE001 — malformed io grades 0.0 fast
            pass
        worst = float(task.get("timeout", 8.0)) * n_cases + 5.0
        budget = max(budget, worst)
    return budget


def grade_task(task: Dict[str, Any],
               languages: Optional[List[str]] = None) -> Dict[str, Any]:
    """Grade ONE {task, generated, solutions|input_output} dict ->
    {score, verdict}. Synchronous — the service runs it on its pool; the
    local fallback path (rewards/client.py) runs it on the caller's
    thread. The SAME dispatch both sides, so fallback outputs are
    bit-identical to fleet outputs for supported tasks."""
    kind = task.get("task", "math")
    try:
        if kind in ("math", "stem"):
            score = math_verify.verify_math(
                task["generated"], task.get("solutions", [])
            )
        elif kind == "code":
            language = task.get("language", "python")
            if (languages is not None and language not in languages) or \
                    language not in code_verify.GRADERS:
                return {"score": 0.0, "verdict": "unsupported_language"}
            score = code_verify.verify_code(
                task["generated"], task.get("input_output", "{}"),
                timeout=float(task.get("timeout", 8.0)),
                language=language,
            )
        else:
            logger.warning(f"unknown reward task kind {kind}; 0 reward")
            return {"score": 0.0, "verdict": "error"}
    except Exception as e:  # noqa: BLE001 — a bad task must not 500
        logger.warning(f"grading failed ({kind}): {e}")
        return {"score": 0.0, "verdict": "error"}
    return {"score": float(score),
            "verdict": "pass" if score > 0 else "fail"}


class RewardService:
    """One sandbox fleet member: bounded concurrent grading + telemetry.

    ``grade_fn`` is the test seam (chaos tests arm slow/failing graders
    without real subprocesses); production uses :func:`grade_task`.
    """

    def __init__(self, cfg, telemetry_sink=None,
                 grade_fn=None):  # cfg: RewardServiceConfig
        self.cfg = cfg
        self.telemetry = telemetry_sink if telemetry_sink is not None \
            else telemetry.NULL
        self._grade_fn = grade_fn or (
            lambda task: grade_task(task, languages=list(cfg.languages))
        )
        self._pool = cf.ThreadPoolExecutor(
            max_workers=max(int(cfg.pool_size), 1),
            thread_name_prefix="reward-grade",
        )
        # Admission bound AND the self-heal threshold: with every
        # admitted grade wedged (each withholding its permit) the pool
        # must be replaced — comparing against pool_size alone would
        # deadlock configs with max_inflight < pool_size (admission
        # exhausted at max_inflight zombies, trigger never reached).
        self._admit_limit = max(
            1, min(int(cfg.max_inflight), int(cfg.pool_size))
        )
        # Created lazily inside the serving loop (asyncio primitives bind
        # the running loop).
        self._sem: Optional[asyncio.Semaphore] = None
        self._inflight = 0
        self._graded = 0
        self._timeouts = 0
        # Timed-out grades whose pool thread is still running (wait_for
        # cannot kill a thread). Each WITHHOLDS its admission permit —
        # released only when the zombie thread finishes or the pool is
        # replaced — so admitted work always has a free thread and the
        # wall budget never times executor-queue wait. At pool_size
        # zombies the pool is replaced wholesale (_replace_pool).
        self._withheld = 0
        # Bumped on pool replacement: a stale zombie's completion
        # callback must not release a permit the replacement already
        # restored.
        self._pool_gen = 0
        self._t_start = time.monotonic()

    # ---------------- grading ----------------

    def _replace_pool(self) -> None:
        """Self-heal from grader-thread leakage: a timed-out grade's
        thread cannot be killed (wait_for abandons, the thread runs on);
        once EVERY thread is a zombie the worker would brick — each new
        grade queuing behind the wedge and timing out in turn. Swap in a
        fresh executor (old one drains unawaited in the background,
        bounded by the sandbox rlimits underneath) and carry on."""
        old = self._pool
        self._pool = cf.ThreadPoolExecutor(
            max_workers=max(int(self.cfg.pool_size), 1),
            thread_name_prefix="reward-grade",
        )
        # The fresh pool has free threads again: restore every withheld
        # permit and invalidate the old zombies' completion callbacks.
        self._pool_gen += 1
        for _ in range(self._withheld):
            self._sem.release()
        self._withheld = 0
        self.telemetry.set_gauge("reward/abandoned_threads", 0)
        self.telemetry.inc("reward/pool_replaced")
        logger.warning(
            "reward grader pool replaced: every thread was wedged past "
            "its grade budget (zombie graders keep draining off-pool)"
        )
        old.shutdown(wait=False)

    async def grade(self, task: Dict[str, Any]) -> Dict[str, Any]:
        """Grade one task under the inflight cap + wall budget."""
        if self._sem is None:
            # Admission is clamped to the thread count: an admitted task
            # starts grading IMMEDIATELY, so the wall budget below times
            # actual grading, never executor-queue wait (tasks admitted
            # beyond the pool would burn their budget queueing and
            # time out without ever running).
            self._sem = asyncio.Semaphore(self._admit_limit)
        kind = task.get("task", "math")
        loop = asyncio.get_running_loop()
        await self._sem.acquire()
        withheld = False
        try:
            self._inflight += 1
            self.telemetry.set_gauge("reward/inflight", self._inflight)
            t0 = time.monotonic()
            try:
                # The THREAD's future: the asyncio wrapper awaited below is
                # cancelled — done — the moment its wait times out, while
                # the thread runs on; only this one says when it ends.
                fut = self._pool.submit(self._grade_fn, task)
                try:
                    out = await asyncio.wait_for(
                        asyncio.wrap_future(fut),
                        timeout=task_budget_secs(
                            task, self.cfg.grade_timeout_secs
                        ),
                    )
                except asyncio.TimeoutError:
                    # The pool thread cannot be killed (the code
                    # sandbox's own rlimits bound it underneath). Its
                    # admission permit stays WITHHELD until the zombie
                    # finishes — releasing now would admit a grade with
                    # no free thread, which would burn its wall budget
                    # in executor-queue wait and time out spuriously.
                    # (A grade no thread had picked up yet is cancelled
                    # by the wait: done, nothing to withhold.)
                    self._timeouts += 1
                    self.telemetry.inc("reward/timeouts")
                    if not fut.done():
                        self._withhold_permit(fut, loop)
                        withheld = True
                    out = {"score": 0.0, "verdict": "timeout"}
                except asyncio.CancelledError:
                    # Client disconnect / handler cancellation: the
                    # grader thread keeps running just like a timeout —
                    # the permit must ride the thread, not the request.
                    if not fut.done():
                        self._withhold_permit(fut, loop)
                        withheld = True
                    raise
            finally:
                self._inflight -= 1
                self.telemetry.set_gauge("reward/inflight", self._inflight)
        finally:
            if not withheld:
                self._sem.release()
        dt = time.monotonic() - t0
        self._graded += 1
        self.telemetry.inc("reward/requests")
        self.telemetry.inc(
            f"reward/verdicts{{task={kind},verdict={out['verdict']}}}"
        )
        self.telemetry.observe(
            f"reward/grade_latency_secs{{task={kind}}}", dt,
            buckets=_LATENCY_BUCKETS,
        )
        return out

    def _withhold_permit(self, fut, loop) -> None:
        """An admitted grade's thread outlived its request (timeout or
        cancellation): keep its admission permit withheld until the
        thread actually finishes, restoring it via the future's done
        callback — generation-guarded so a pool replacement (which
        restores all withheld permits itself) invalidates stale
        callbacks. Replacement triggers at the ADMISSION limit: the
        point where every admittable slot is withheld and the worker
        would otherwise brick."""
        self._withheld += 1
        self.telemetry.set_gauge("reward/abandoned_threads",
                                 self._withheld)
        gen = self._pool_gen

        def _zombie_done(_f, gen=gen, loop=loop):
            def _restore():
                if self._pool_gen == gen and self._withheld:
                    self._withheld -= 1
                    self.telemetry.set_gauge("reward/abandoned_threads",
                                             self._withheld)
                    self._sem.release()
            try:
                loop.call_soon_threadsafe(_restore)
            except RuntimeError:
                pass  # loop closed: worker shutting down

        fut.add_done_callback(_zombie_done)
        if self._withheld >= self._admit_limit:
            self._replace_pool()

    async def grade_batch(self, tasks: List[Dict[str, Any]]) -> List[Dict]:
        return list(await asyncio.gather(*[self.grade(t) for t in tasks]))

    # ---------------- http handlers ----------------

    async def _handle_verify(self, request, kind: str):
        from aiohttp import web

        try:
            task = await request.json()
        except Exception:  # noqa: BLE001 — malformed body
            return web.json_response(
                {"score": 0.0, "verdict": "error", "error": "bad json"},
                status=400,
            )
        task.setdefault("task", kind)
        return web.json_response(await self.grade(task))

    async def handle_math_verify(self, request):
        return await self._handle_verify(request, "math")

    async def handle_code_verify(self, request):
        return await self._handle_verify(request, "code")

    async def handle_batch(self, request):
        from aiohttp import web

        try:
            body = await request.json()
            tasks = body["tasks"] if isinstance(body, dict) else body
            assert isinstance(tasks, list)
        except Exception:  # noqa: BLE001 — malformed body
            return web.json_response(
                {"error": "expected {tasks: [...]} or a JSON list"},
                status=400,
            )
        outs = await self.grade_batch(tasks)
        return web.json_response({
            "scores": [o["score"] for o in outs],
            "verdicts": [o["verdict"] for o in outs],
        })

    async def handle_health(self, request):
        from aiohttp import web

        return web.json_response({
            "ok": True,
            "inflight": self._inflight,
            "graded_total": self._graded,
            "timeouts_total": self._timeouts,
            "languages": list(self.cfg.languages),
            "uptime_secs": time.monotonic() - self._t_start,
        })

    def metrics_dict(self) -> Dict[str, Any]:
        return {
            "reward_graded": self._graded,
            "reward_timeout_count": self._timeouts,
            "reward_inflight": self._inflight,
            "reward_pool_size": self.cfg.pool_size,
        }

    def build_app(self, extra_metrics=None, labels=None):
        """The aiohttp application. ``extra_metrics``/``labels`` let the
        wrapping worker (system/reward_worker.py) add its identity to the
        Prometheus exposition without this core knowing about workers."""
        from aiohttp import web

        async def handle_metrics(request):
            body = telemetry.render_prometheus(
                self.telemetry.snapshot(reset=False),
                extra_gauges={**self.metrics_dict(),
                              **((extra_metrics() if extra_metrics else {}))},
                labels=labels,
            )
            return web.Response(
                text=body, content_type="text/plain", charset="utf-8",
                headers={"X-Prometheus-Version": "0.0.4"},
            )

        async def handle_metrics_json(request):
            return web.json_response({
                **self.metrics_dict(),
                **((extra_metrics() if extra_metrics else {})),
            })

        app = web.Application(client_max_size=64 * 1024 * 1024)
        app.router.add_post("/math_verify", self.handle_math_verify)
        app.router.add_post("/code_verify", self.handle_code_verify)
        app.router.add_post("/batch_reward", self.handle_batch)
        app.router.add_get("/health", self.handle_health)
        app.router.add_get("/metrics", handle_metrics)
        app.router.add_get("/metrics.json", handle_metrics_json)
        return app

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
