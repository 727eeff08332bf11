"""Serving driver: continuous batching over the compiled serving programs.

Policy layer only — a fixed pool of ``slots`` sequences decodes in
lock-step through ONE compiled decode program; finished sequences release
their slot to the next queued request (continuous batching). All execution
and slot-state surgery lives in :class:`repro.exec.serving.ServeEngine`:

  * admission runs ONE batched prefill over the newly admitted requests
    (bucketed compile cache on ``(batch bucket, length bucket)``) and
    splices each row's K/V cache into its slot;
  * position bookkeeping is per-slot (``cache["pos"]`` is a vector), so a
    pad-token tick on an idle slot never advances or overwrites another
    slot's rows;
  * each request's first token is seeded from its OWN prefill logits row;
  * slots are zeroed on release and re-spliced on reuse.

Invariant (tests/test_serve.py): staggered multi-slot serving produces
byte-identical token streams to sequential decode, one request at a time
(:func:`sequential_reference`: the tests run it single-slot; ``--check``
and the chip smoke at the served slot count, since a TPU's decode
rounding depends on the batch shape).

Resilience (``resilience=ResilienceConfig()`` / ``--resilience``): the
driver treats faults and overload as normal control flow instead of
crashing, with *byte-identical* recovered outputs (prompts are
deterministic, every program is row-independent, so replay-from-prompt
reproduces the fault-free stream bit for bit — the ``chaos_micro`` CI
gate's contract). Every request ends in exactly one terminal status:

  ``ok``       decoded to completion (the only status with output);
  ``expired``  its SLO deadline (``Request.deadline_ticks``, driver
               ticks since submit) passed while queued or in flight;
  ``shed``     admission control: even an immediate admission could not
               finish inside the deadline, so the request is rejected
               up front instead of wasting slot time;
  ``failed``   the numerical watchdog quarantined it more than
               ``max_replays`` times.

The degradation ladder, in order of escalation:

  1. **bounded retries** — a raising compiled program (decode, prefill,
     splice) is retried up to ``max_retries`` times with exponential
     backoff; a one-shot fault clears deterministically;
  2. **numerical watchdog** — NaN/Inf decode logits or prefill rows
     quarantine the offending slot only: the slot is zeroed through the
     jitted reset path and the request replays from its prompt (healthy
     neighbours are untouched — row independence);
  3. **graceful degradation** — ``degrade_after`` consecutive
     engine-level failures switch the driver to the per-request
     teacher-forced path (``ServeEngine.decode_single``), which finishes
     one request per tick on a private single-row state; each degraded
     tick also probes the batched program, and ``recover_after``
     consecutive clean probes switch back to the compiled path;
  4. **snapshot/restore** — with ``snapshot_dir`` the driver writes a
     periodic integrity-checked serving snapshot (the slot cache plus a
     JSON driver record) through ``repro.checkpoint.manager``; after a
     mid-workload crash :meth:`Server.resume` restores finished outputs
     and re-queues in-flight requests for replay (bit-identical again).

Every fault, retry, shed, expiry, quarantine and degradation transition
is counted in ``repro.obs`` metrics (``serve_faults{site}``,
``serve_retries{site}``, ``serve_requests{status}``,
``serve_quarantines``, ``serve_degraded_transitions{to}``) and emitted
as ``resilience``-category trace instants, so ``python -m
repro.obs.report`` shows the fault timeline next to the latency
breakdown.

Observability: ``--trace PATH`` (or ``Server(tracer=...)``) records the
per-request lifecycle (submit -> queue -> prefill -> first token ->
decode ticks -> finish, as nested ``request``-category spans) plus a
per-tick ``slots`` occupancy counter track into a ``repro.obs`` trace —
Chrome/Perfetto-loadable, summarized by ``python -m repro.obs.report``,
and carrying the tick indices ``repro.sim`` replays. ``Server.stats()``
reports the same percentiles (shared ``repro.obs.metrics.percentile``)
and is well-formed at any point in the server's life;
``Server.metrics_dict()`` emits the unified metrics schema.

Fault injection is deterministic data, not monkeypatching: pass a
``repro.runtime.chaos.ChaosInjector`` (``--chaos "decode@4=raise;..."``)
and the engine's decode/prefill/splice/reset sites plus the driver's
tick loop fire the spec's faults at exact invocation indices.

Mesh serving: ``--mesh D`` (or ``DxM``) runs the engine's data-parallel
mode — the slot axis of every serve-state leaf shards over the mesh's
data axis, params replicate, and the same invariant holds per slot
(tests/test_exec_sharded.py). On CPU hosts fake the devices first::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.serve --mesh 8 --check
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.exec.serving import ServeEngine
from repro.models import api
from repro.obs.metrics import Metrics, percentile

TERMINAL_STATUSES = ("ok", "expired", "shed", "failed")


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    done_at: float = 0.0
    # driver tick indices (the trace's replay clock: repro.sim consumes
    # ticks, not wall seconds)
    submitted_tick: int = -1
    admitted_tick: int = -1
    done_tick: int = -1
    # resilience: SLO deadline in driver ticks since submit (None = no
    # SLO), lifecycle status (queued -> active -> one of
    # TERMINAL_STATUSES), and how many times the watchdog replayed it
    deadline_ticks: Optional[int] = None
    status: str = "queued"
    replays: int = 0


@dataclass
class ResilienceConfig:
    """Knobs for the serving resilience layer (see module docstring).

    ``max_retries``     per-site compiled-program retries within a tick;
    ``retry_backoff_s`` base of the exponential retry backoff;
    ``max_replays``     watchdog prompt-replays before ``failed``;
    ``degrade_after``   consecutive engine failures before falling back
                        to the per-request teacher-forced path;
    ``recover_after``   consecutive clean probes before returning to the
                        compiled path;
    ``watchdog``        NaN/Inf checks on decode logits + prefill rows;
    ``shed``            admission control: shed queued requests whose
                        deadline has become infeasible.
    """

    max_retries: int = 2
    retry_backoff_s: float = 0.005
    max_replays: int = 3
    degrade_after: int = 3
    recover_after: int = 2
    watchdog: bool = True
    shed: bool = True


def _pct(xs, q):
    """Percentile through the shared repro.obs implementation — the same
    arithmetic the trace report CLI uses, so `Server.stats()` and
    `python -m repro.obs.report` agree bit for bit. Well-formed on zero
    ([] -> 0.0) and one ([x] -> x) samples."""
    return percentile(xs, q)


# serve-latency histogram buckets (seconds): 100us .. ~100s, geometric
_LAT_BUCKETS = [1e-4 * (10 ** 0.5) ** i for i in range(13)]


class Server:
    def __init__(self, arch: str, *, smoke: bool = True, slots: int = 4,
                 max_len: int = 128, greedy: bool = True,
                 bos_id: Optional[int] = 0, mesh=None, tracer=None,
                 resilience: Optional[ResilienceConfig] = None,
                 chaos=None, snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0):
        self.cfg = configs.get(arch, smoke=smoke)
        self.model = api.build(self.cfg)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        self.bos_id = bos_id
        if self.cfg.family == "encdec":
            raise NotImplementedError(
                "serve driver demos decoder-only archs; encdec uses "
                "encode+decode_step directly (see tests)")
        # observability: the tracer (optional) records the per-request
        # lifecycle + per-tick slot occupancy; the metrics registry is
        # always on (cheap counters) and feeds metrics_dict()
        self.tracer = tracer
        if tracer is not None:
            tracer.meta.update(kind="serve", arch=arch, slots=slots,
                               max_len=max_len)
        self.metrics = Metrics()
        # resilience: None disables the whole layer (retries, watchdog,
        # shedding, degradation) — the fault-free hot path then runs the
        # PR-4 code byte for byte
        self.resilience = resilience
        self.chaos = chaos
        if chaos is not None:
            chaos.observe(self.metrics, tracer)
        self.engine = ServeEngine(self.model, slots=slots, max_len=max_len,
                                  mesh=mesh, tracer=tracer, chaos=chaos)
        self.params = self.engine.shard_params(self.params)
        self.cache = self.engine.init_state()
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.slot_remaining = np.zeros(slots, np.int32)
        self.tokens = np.zeros((slots, 1), np.int32)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.tokens_prefill = 0
        self.tokens_decode = 0
        self.ticks = 0
        self.submitted = 0
        # resilience state: consecutive engine-level failures, degraded
        # flag, consecutive clean probes while degraded, plain-int views
        # of the fault counters for cheap stats()
        self.degraded = False
        self._engine_failures = 0
        self._probe_ok = 0
        self.n_faults = 0
        self.n_retries = 0
        self.n_quarantines = 0
        self.n_degraded_transitions = 0
        # serving snapshots (resume after a mid-workload crash)
        self.snapshot_every = int(snapshot_every)
        self._snap = None
        if snapshot_dir:
            from repro.checkpoint.manager import CheckpointManager
            self._snap = CheckpointManager(snapshot_dir, keep_n=3)
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        """Queue a request. Empty prompts are defined here, once: seed a
        BOS token (``bos_id``) or reject when the server has none."""
        if not req.prompt:
            if self.bos_id is None:
                raise ValueError("empty prompt and no bos_id configured")
            req.prompt = [self.bos_id]
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1 "
                             f"(got {req.max_new})")
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds max_len {self.max_len}")
        if req.deadline_ticks is not None and req.deadline_ticks < 0:
            raise ValueError(f"request {req.rid}: deadline_ticks must be "
                             f">= 0 (got {req.deadline_ticks})")
        req.submitted_at = time.perf_counter()
        req.submitted_tick = self.ticks
        req.status = "queued"
        self.submitted += 1
        self.queue.append(req)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("submit", cat="serve",
                       attrs={"rid": req.rid, "prompt_len": len(req.prompt),
                              "max_new": req.max_new, "tick": self.ticks})

    # -- terminal bookkeeping ------------------------------------------
    def _finish(self, req: Request, status: str):
        """The ONE place a request reaches a terminal status."""
        req.status = status
        req.done_at = time.perf_counter()
        req.done_tick = self.ticks
        self.finished.append(req)
        self.metrics.counter("serve_requests", status=status).inc()
        if status == "ok":
            self._observe_finished(req)
        else:
            self._instant("evict", {"rid": req.rid, "status": status})

    def _release(self, s: int, status: str = "ok"):
        req = self.slot_req[s]
        self.slot_req[s] = None
        self.tokens[s, 0] = 0
        self._reset_slot_safe(s)
        self._finish(req, status)

    def _reset_slot_safe(self, s: int):
        """Zero a released slot. Resilient mode tolerates a failing
        reset program: the next admission's splice overwrites the whole
        slot row anyway (splice pads prompt rows to max_len), so a
        skipped zeroing cannot leak state into a later request."""
        if self.resilience is None:
            self.cache = self.engine.reset_slot(self.cache, s)
            return
        try:
            self.cache = self._attempt(
                "reset", lambda: self.engine.reset_slot(self.cache, s))
        except Exception:                        # noqa: BLE001
            self._engine_failure()

    def _observe_finished(self, req: Request):
        """Emit the request's lifecycle into metrics + trace. The trace
        schema (repro.obs.trace docstring) is the replayable one: args
        carry rid / prompt_len / max_new / out_len plus the tick indices
        repro.sim replays and the measured waits in seconds."""
        queue_wait = req.admitted_at - req.submitted_at
        ttft = req.first_token_at - req.submitted_at
        latency = req.done_at - req.submitted_at
        m = self.metrics
        m.counter("serve_tokens", kind="out").inc(len(req.out))
        m.histogram("serve_queue_wait_s", _LAT_BUCKETS).observe(queue_wait)
        m.histogram("serve_ttft_s", _LAT_BUCKETS).observe(ttft)
        m.histogram("serve_latency_s", _LAT_BUCKETS).observe(latency)
        tr = self.tracer
        if tr is None or not tr.enabled:
            return
        attrs = {"rid": req.rid, "prompt_len": len(req.prompt),
                 "max_new": req.max_new, "out_len": len(req.out),
                 "submit_tick": req.submitted_tick,
                 "admit_tick": req.admitted_tick,
                 "done_tick": req.done_tick,
                 "queue_wait_s": queue_wait, "ttft_s": ttft,
                 "latency_s": latency}
        pid = tr.add_span("request", "request", req.submitted_at,
                          req.done_at, attrs=attrs)
        rid = {"rid": req.rid}
        tr.add_span("queue", "request", req.submitted_at, req.admitted_at,
                    parent=pid, attrs=rid)
        tr.add_span("prefill", "request", req.admitted_at,
                    req.first_token_at, parent=pid, attrs=rid)
        tr.add_span("decode", "request", req.first_token_at, req.done_at,
                    parent=pid, attrs=rid)

    # -- resilience plumbing -------------------------------------------
    def _instant(self, name: str, attrs: Dict):
        tr = self.tracer
        if tr is not None and tr.enabled:
            a = {"tick": self.ticks}
            a.update(attrs)
            tr.instant(name, cat="resilience", attrs=a)

    def _note_fault(self, site: str, err: Exception):
        self.n_faults += 1
        self.metrics.counter("serve_faults", site=site).inc()
        self._instant("fault", {"site": site,
                                "error": type(err).__name__})

    def _attempt(self, site: str, fn):
        """Run ``fn`` under the bounded-retry policy: every raise is
        counted as a fault; retries back off exponentially; the last
        error re-raises for the caller's escalation path."""
        res = self.resilience
        last = None
        for attempt in range(res.max_retries + 1):
            if attempt:
                time.sleep(res.retry_backoff_s * (2 ** (attempt - 1)))
                self.n_retries += 1
                self.metrics.counter("serve_retries", site=site).inc()
                self._instant("retry", {"site": site, "attempt": attempt})
            try:
                return fn()
            except Exception as e:               # noqa: BLE001
                last = e
                self._note_fault(site, e)
        raise last

    def _engine_failure(self):
        """An engine call exhausted its retries. Enough of these in a
        row escalate to the degraded (per-request teacher-forced)
        path."""
        res = self.resilience
        self._engine_failures += 1
        if not self.degraded and \
                self._engine_failures >= res.degrade_after:
            self.degraded = True
            self._probe_ok = 0
            self.n_degraded_transitions += 1
            self.metrics.counter("serve_degraded_transitions",
                                 to="degraded").inc()
            self._instant("degrade",
                          {"failures": self._engine_failures})

    def _expire_and_shed(self):
        """SLO enforcement, once per tick before admission. In-flight or
        queued requests whose deadline has passed are evicted
        (``expired``); queued requests that could not finish even if
        admitted THIS tick (done tick would be ``ticks + max_new - 1``)
        are shed up front (``shed``) instead of wasting slot time."""
        res = self.resilience
        for s in range(self.slots):
            req = self.slot_req[s]
            if req is not None and req.deadline_ticks is not None and \
                    self.ticks - req.submitted_tick > req.deadline_ticks:
                self.metrics.counter("serve_expired").inc()
                self._release(s, "expired")
        keep = []
        for req in self.queue:
            if req.deadline_ticks is not None:
                age = self.ticks - req.submitted_tick
                if age > req.deadline_ticks:
                    self.metrics.counter("serve_expired").inc()
                    self._finish(req, "expired")
                    continue
                if res.shed and \
                        age + req.max_new - 1 > req.deadline_ticks:
                    self.metrics.counter("serve_shed").inc()
                    self._instant("shed", {"rid": req.rid,
                                           "deadline": req.deadline_ticks,
                                           "age": age})
                    self._finish(req, "shed")
                    continue
            keep.append(req)
        self.queue = keep

    def _quarantine(self, s: int):
        """Watchdog hit on slot ``s``: zero the slot through the jitted
        reset path and replay the request from its prompt (deterministic
        prompts -> bit-identical replay), or fail it once the replay
        budget is spent. Healthy slots are untouched."""
        req = self.slot_req[s]
        self.slot_req[s] = None
        self.tokens[s, 0] = 0
        self._reset_slot_safe(s)
        req.replays += 1
        self.n_quarantines += 1
        self.metrics.counter("serve_quarantines").inc()
        self._instant("quarantine", {"rid": req.rid, "slot": s,
                                     "replays": req.replays})
        if req.replays > self.resilience.max_replays:
            self._finish(req, "failed")
        else:
            req.out = []
            req.status = "queued"
            self.queue.insert(0, req)

    # -- admission ------------------------------------------------------
    def _admit(self):
        """Fill free slots from the queue with ONE batched prefill.

        Each admitted request's KV rows are spliced into its own slot and
        its first token comes from its OWN prefill logits row — admission
        never touches occupied slots (per-slot positions + row splicing;
        the engine enforces it structurally). Resilient mode wraps the
        prefill/splice programs in the retry policy (a still-failing
        admission re-queues the batch untouched for the next tick) and
        watchdogs the prefill rows: a NaN row re-queues only that
        request; its neighbours admit normally."""
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        take = self.queue[: len(free)]
        if not take:
            return
        del self.queue[: len(take)]
        now = time.perf_counter()
        for req in take:
            req.admitted_at = now
            req.admitted_tick = self.ticks
            req.status = "active"
        res = self.resilience
        if res is None:
            logits, rows, n = self.engine.prefill(
                self.params, [r.prompt for r in take])
            self.cache = self.engine.splice_many(self.cache, free[:n], rows)
            good = list(range(n))
        else:
            try:
                logits, rows, n = self._attempt(
                    "prefill", lambda: self.engine.prefill(
                        self.params, [r.prompt for r in take]))
            except Exception:                    # noqa: BLE001
                for req in take:
                    req.status = "queued"
                self.queue[:0] = take            # back to the front, in order
                self._engine_failure()
                return
            good = list(range(n))
            lgn = None
            if res.watchdog:
                lgn = np.asarray(jnp.asarray(logits)[:n])
                finite = np.isfinite(lgn).all(
                    axis=tuple(range(1, lgn.ndim)))
                good = [j for j in range(n) if finite[j]]
                for j in range(n):
                    if not finite[j]:
                        self._quarantine_admission(take[j])
            if not good:
                return
            try:
                self.cache = self._attempt(
                    "splice", lambda: self.engine.splice_many(
                        self.cache, [free[i] for i in range(len(good))],
                        rows, js=good))
            except Exception:                    # noqa: BLE001
                for j in good:
                    take[j].status = "queued"
                self.queue[:0] = [take[j] for j in good]
                self._engine_failure()
                return
            self._engine_failures = 0
        if not self.greedy:
            firsts = np.zeros(n, np.int64)
        elif res is not None and res.watchdog:
            firsts = lgn.argmax(axis=-1)       # reuse the watchdog transfer
        else:
            firsts = np.asarray(jnp.argmax(logits[:n], axis=-1))
        for i, j in enumerate(good):
            s, req = free[i], take[j]
            first = int(firsts[j])
            req.out.append(first)
            req.first_token_at = time.perf_counter()
            self.tokens_prefill += len(req.prompt)
            self.metrics.counter("serve_tokens",
                                 kind="prefill").inc(len(req.prompt))
            self.slot_req[s] = req
            self.slot_remaining[s] = req.max_new - 1
            self.tokens[s, 0] = first
            if self.slot_remaining[s] <= 0:     # max_new == 1: done already
                self._release(s)

    def _quarantine_admission(self, req: Request):
        """A NaN prefill row never reaches a slot: replay from prompt or
        fail, exactly like a decode-time quarantine (minus the reset —
        nothing was spliced)."""
        req.replays += 1
        self.n_quarantines += 1
        self.metrics.counter("serve_quarantines").inc()
        self._instant("quarantine", {"rid": req.rid, "slot": -1,
                                     "replays": req.replays})
        if req.replays > self.resilience.max_replays:
            self._finish(req, "failed")
        else:
            req.out = []
            req.status = "queued"
            self.queue.insert(0, req)

    # -- the tick -------------------------------------------------------
    def tick(self) -> int:
        """One decode step for the whole slot batch; returns #tokens
        produced this tick (0 on a stalled tick).

        With a tracer attached each tick is a ``serve``-category span
        (admission + decode nested inside it) followed by one sample of
        the ``slots`` counter track — the per-tick slot-occupancy series
        the trace report turns into utilization."""
        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("tick", cat="serve", attrs={"tick": self.ticks}):
                n = self._tick_inner()
            # the tick index rides on the counter sample so the
            # Trace.serve_ticks() iterator is self-indexing (replay does
            # not need to join against the tick spans)
            tr.counter("slots", {"active": n, "queued": len(self.queue),
                                 "tick": self.ticks})
        else:
            n = self._tick_inner()
        self.ticks += 1
        self.metrics.counter("serve_ticks").inc()
        self.metrics.counter("serve_tokens", kind="decode").inc(n)
        self.metrics.gauge("serve_slots_active").set(n)
        return n

    def _tick_inner(self) -> int:
        if self.chaos is not None:
            # tick-site faults: latency spikes stall the driver loop;
            # a raise here IS the mid-workload crash (snapshot/resume)
            self.chaos.enter("tick")
        if self.resilience is not None:
            self._expire_and_shed()
            if self.degraded:
                n = self._tick_degraded()
                self._maybe_snapshot()
                return n
        n = self._tick_compiled()
        self._maybe_snapshot()
        return n

    def _tick_compiled(self) -> int:
        self._admit()
        active = [s for s in range(self.slots)
                  if self.slot_req[s] is not None]
        if not active:
            return 0
        res = self.resilience
        if res is None:
            logits, self.cache = self.engine.decode(
                self.params, jnp.asarray(self.tokens), self.cache)
            nxt = (np.asarray(jnp.argmax(logits[:, -1], axis=-1))
                   if self.greedy else np.zeros(self.slots, np.int64))
        else:
            try:
                logits, cache = self._attempt(
                    "decode", lambda: self.engine.decode(
                        self.params, jnp.asarray(self.tokens), self.cache))
            except Exception:                    # noqa: BLE001
                # no progress this tick; nothing was committed (the
                # programs are functional), so the next tick retries
                # from an unchanged state
                self._engine_failure()
                return 0
            self._engine_failures = 0
            self.cache = cache
            # ONE device->host transfer serves both the watchdog and the
            # argmax (host argmax == XLA argmax: first maximum wins in
            # both; the chaos differential gate verifies byte-identity
            # against the jnp.argmax reference path empirically)
            lgn = np.asarray(jnp.asarray(logits)[:, -1])
            if res.watchdog:
                finite = np.isfinite(lgn).all(axis=-1)
                bad = [s for s in active if not finite[s]]
                if bad:
                    for s in bad:
                        self._quarantine(s)
                    active = [s for s in active if finite[s]]
                    if not active:
                        return 0
            nxt = (lgn.argmax(axis=-1) if self.greedy
                   else np.zeros(self.slots, np.int64))
        for s in active:
            req = self.slot_req[s]
            req.out.append(int(nxt[s]))
            self.tokens_decode += 1
            self.tokens[s, 0] = int(nxt[s])
            self.slot_remaining[s] -= 1
            if self.slot_remaining[s] <= 0:
                self._release(s)
        return len(active)

    def _tick_degraded(self) -> int:
        """Degraded mode: the batched decode program is considered down.
        Each tick (1) probes it on the live state — results discarded,
        the programs are functional — and recovers to the compiled path
        after ``recover_after`` consecutive clean probes; (2) finishes
        ONE request end to end through the per-request teacher-forced
        path, so the server keeps draining under a persistent fault."""
        res = self.resilience
        try:
            self.engine.decode(self.params, jnp.asarray(self.tokens),
                               self.cache)
            self._probe_ok += 1
        except Exception as e:                   # noqa: BLE001
            self._probe_ok = 0
            self._note_fault("probe", e)
        if self._probe_ok >= res.recover_after:
            self.degraded = False
            self._engine_failures = 0
            self.n_degraded_transitions += 1
            self.metrics.counter("serve_degraded_transitions",
                                 to="compiled").inc()
            self._instant("recover", {"probes": self._probe_ok})
            return self._tick_compiled()
        req = None
        held = None
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                req, held = self.slot_req[s], s
                break
        if req is None and self.queue:
            req = self.queue.pop(0)
            req.admitted_at = time.perf_counter()
            req.admitted_tick = self.ticks
            req.status = "active"
            self.tokens_prefill += len(req.prompt)
            self.metrics.counter("serve_tokens",
                                 kind="prefill").inc(len(req.prompt))
        if req is None:
            return 0
        try:
            out = self.engine.decode_single(self.params, req.prompt,
                                            req.max_new)
        except Exception as e:                   # noqa: BLE001
            self._note_fault("fallback", e)
            if held is None:
                req.status = "queued"
                self.queue.insert(0, req)        # retried next tick
            return 0
        # the full replay (greedy, deterministic) subsumes any tokens the
        # compiled path already produced — same stream, bit for bit
        req.out = list(out)
        req.first_token_at = time.perf_counter()
        self.metrics.counter("serve_requests_degraded").inc()
        if held is not None:
            self.slot_req[held] = None
            self.tokens[held, 0] = 0
            self._reset_slot_safe(held)
        self._finish(req, "ok")
        return 1

    # -- serving snapshots ---------------------------------------------
    def _maybe_snapshot(self):
        if self._snap is not None and self.snapshot_every and \
                (self.ticks + 1) % self.snapshot_every == 0:
            self.snapshot()

    def snapshot(self):
        """Write a serving snapshot through the checkpoint manager: the
        slot cache as the (integrity-checked, atomically renamed) array
        tree, the driver record — finished outputs plus every
        still-pending request's prompt — as the manifest's extra
        payload. Restore replays pending requests from their prompts
        (deterministic, so the resumed run's outputs are bit-identical);
        the cache array is there for integrity verification and
        forensics, not resumption."""
        if self._snap is None:
            raise RuntimeError("no snapshot_dir configured")
        pending = [r for r in self.slot_req if r is not None] + self.queue
        pending.sort(key=lambda r: (r.submitted_tick, r.rid))
        rec = {
            "ticks": self.ticks,
            "submitted": self.submitted,
            "pending": [{"rid": r.rid, "prompt": list(r.prompt),
                         "max_new": r.max_new,
                         "deadline_ticks": r.deadline_ticks}
                        for r in pending],
            "finished": [{"rid": r.rid, "prompt": list(r.prompt),
                          "max_new": r.max_new, "out": list(r.out),
                          "status": r.status}
                         for r in self.finished],
        }
        self._snap.save(self.ticks, {"cache": self.cache},
                        extra={"serving": rec})
        self.metrics.counter("serve_snapshots").inc()
        self._instant("snapshot", {"step": self.ticks})

    @classmethod
    def resume(cls, arch: str, snapshot_dir: str, **kw) -> "Server":
        """Rebuild a server from the newest integrity-clean snapshot in
        ``snapshot_dir``: finished requests are restored with their
        outputs and statuses; in-flight and queued requests are
        re-queued for replay from their prompts. With no verified
        snapshot the server starts fresh."""
        srv = cls(arch, snapshot_dir=snapshot_dir, **kw)
        step, meta = srv._snap.verified_meta()
        if meta is None or "serving" not in meta:
            return srv
        rec = meta["serving"]
        for f in rec.get("finished", []):
            req = Request(rid=f["rid"], prompt=list(f["prompt"]),
                          max_new=f["max_new"])
            req.out = list(f["out"])
            req.status = f["status"]
            srv.finished.append(req)
        now = time.perf_counter()
        for p in rec.get("pending", []):
            req = Request(rid=p["rid"], prompt=list(p["prompt"]),
                          max_new=p["max_new"],
                          deadline_ticks=p.get("deadline_ticks"))
            req.submitted_at = now
            req.submitted_tick = 0
            srv.queue.append(req)
        srv.submitted = int(rec.get("submitted",
                                    len(srv.finished) + len(srv.queue)))
        srv._instant("resume", {"snapshot_step": step,
                                "replayed": len(srv.queue)})
        return srv

    # ------------------------------------------------------------------
    def run_workload(self, requests: List[Request], stagger_ticks: int = 0,
                     max_ticks: int = 10_000) -> Dict:
        """Submit ``requests[i]`` once ``i * stagger_ticks`` ticks have
        elapsed (0 = all up front), then drain."""
        t0 = time.perf_counter()
        ticks = 0
        i = 0
        while (i < len(requests) or self.queue
               or any(r is not None for r in self.slot_req)):
            while i < len(requests) and ticks >= i * stagger_ticks:
                self.submit(requests[i])
                i += 1
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("server did not drain")
        return self._report(time.perf_counter() - t0, ticks)

    def run_until_drained(self, max_ticks: int = 10_000) -> Dict:
        return self.run_workload([], 0, max_ticks)

    def reset_stats(self):
        """Clear finished requests and token counters (benchmarking: time a
        warm workload without the first run's compiles). The server must be
        drained first; compiled programs and slot state stay warm."""
        if self.queue or any(r is not None for r in self.slot_req):
            raise RuntimeError("reset_stats on a busy server")
        self.finished = []
        self.tokens_prefill = 0
        self.tokens_decode = 0
        self.ticks = 0
        self.submitted = 0
        self.n_faults = 0
        self.n_retries = 0
        self.n_quarantines = 0
        self.n_degraded_transitions = 0
        self.metrics = Metrics()
        if self.chaos is not None:
            self.chaos.observe(self.metrics, self.tracer)
        self._t0 = time.perf_counter()

    def reset_state(self):
        """reset_stats + a factory-fresh slot cache, keeping the compiled
        programs warm — a reused server becomes indistinguishable from a
        newly built one (sequential_reference relies on this)."""
        self.reset_stats()
        self.cache = self.engine.init_state()
        self.slot_remaining[:] = 0
        self.tokens[:] = 0
        self.degraded = False
        self._engine_failures = 0
        self._probe_ok = 0

    def stats(self, wall_s: Optional[float] = None,
              ticks: Optional[int] = None) -> Dict:
        """Current serving stats — callable at ANY point in the server's
        life and well-formed for zero or one finished request (empty
        percentile lists report 0.0; a single sample is its own p50 and
        p99 — the :func:`repro.obs.metrics.percentile` contract, shared
        with the trace report CLI so the two agree bit for bit).
        Defaults: wall time since construction / last ``reset_stats``,
        tick count since the same.

        Status accounting invariant (tests/test_serve.py): the
        ``statuses`` counts plus ``queued`` plus ``active`` always sum
        to ``requests_submitted`` — every submitted request is exactly
        one of: terminal, waiting, or in a slot. Latency percentiles are
        computed over ``ok`` requests only (evicted requests have no
        meaningful first-token/done timestamps)."""
        fin = self.finished
        if wall_s is None:
            wall_s = time.perf_counter() - self._t0
        if ticks is None:
            ticks = self.ticks
        statuses = {st: 0 for st in TERMINAL_STATUSES}
        for r in fin:
            statuses[r.status] = statuses.get(r.status, 0) + 1
        ok = [r for r in fin if r.status == "ok"]
        tokens_out = sum(len(r.out) for r in ok)
        total = self.tokens_prefill + tokens_out
        queue_wait = [r.admitted_at - r.submitted_at for r in ok]
        ttft = [r.first_token_at - r.submitted_at for r in ok]
        lat = [r.done_at - r.submitted_at for r in ok]
        return {
            "requests": len(fin),
            "requests_submitted": self.submitted,
            "statuses": statuses,
            "queued": len(self.queue),
            "active": sum(1 for r in self.slot_req if r is not None),
            "ticks": ticks,
            "tokens_prefill": self.tokens_prefill,
            "tokens_decode": self.tokens_decode,
            "tokens_out": tokens_out,
            "tokens_total": total,
            "wall_s": wall_s,
            "tok_per_s": total / wall_s if wall_s else 0.0,
            "tok_per_s_out": tokens_out / wall_s if wall_s else 0.0,
            "p50_queue_wait_s": _pct(queue_wait, 50),
            "p99_queue_wait_s": _pct(queue_wait, 99),
            "p50_ttft_s": _pct(ttft, 50),
            "p99_ttft_s": _pct(ttft, 99),
            "p50_latency_s": _pct(lat, 50),
            "p99_latency_s": _pct(lat, 99),
            "prefill_compiles": self.engine.prefill_compiles,
            "degraded": self.degraded,
            "faults": self.n_faults,
            "retries": self.n_retries,
            "quarantines": self.n_quarantines,
            "degraded_transitions": self.n_degraded_transitions,
        }

    def metrics_dict(self) -> Dict:
        """The same numbers through the unified ``repro.obs.metrics``
        schema (versioned, mergeable across servers/runs)."""
        return self.metrics.to_dict()

    def _report(self, dt: float, ticks: int) -> Dict:
        return self.stats(wall_s=dt, ticks=ticks)


def sequential_reference(arch: str, requests: List[Request], *,
                         slots: int = 1, **server_kw) -> List[List[int]]:
    """Decode every request alone on a server — the byte-level reference
    the continuous-batching outputs must reproduce (with or without
    faults: recovery replays from deterministic prompts). One server is
    built (the programs compile once); its state is factory-reset between
    requests so each decodes against a fresh cache.

    ``slots=1`` also holds the batched server to the single-row decode
    program. Neither the CPU nor a TPU v5e computes a row bitwise alike
    at batch 1 and batch N. In f32 on the CPU the difference (~1e-6) has
    not flipped a greedy argmax in the tests; the bf16 decode on a TPU v5e
    differs by up to a few bf16 steps of the logits (0.055 at a scale of
    ~4) and flips them. There the reference takes the
    served ``slots`` (and ``mesh``): each request still decodes with no
    neighbour, through the same program, which is row-independent
    bitwise."""
    srv = Server(arch, slots=slots, **server_kw)
    outs = []
    for req in requests:
        srv.reset_state()
        srv.submit(Request(rid=req.rid, prompt=list(req.prompt),
                           max_new=req.max_new))
        srv.run_until_drained()
        outs.append(srv.finished[0].out)
    return outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=list(configs.ARCHS))
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (default: the "
                         "reduced smoke config)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--stagger", type=int, default=2,
                    help="ticks between request arrivals (staggered "
                         "workload; 0 = all at once)")
    ap.add_argument("--check", action="store_true",
                    help="re-decode each request alone on a server of "
                         "the same slots and mesh and verify byte-identical "
                         "outputs")
    ap.add_argument("--mesh", default=None,
                    help="data-parallel serving mesh, 'D' or 'DxM' (fake "
                         "host devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the serve trace here: per-request "
                         "lifecycle spans + per-tick slot occupancy. "
                         "'.jsonl' -> the repro.obs JSONL schema, "
                         "anything else -> Chrome trace JSON (open in "
                         "Perfetto); summarize with "
                         "python -m repro.obs.report PATH")
    ap.add_argument("--resilience", action="store_true",
                    help="enable the serving resilience layer (bounded "
                         "retries, NaN watchdog, SLO shedding, graceful "
                         "degradation) with default knobs")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault-injection spec, e.g. "
                         "'decode@4=raise;decode@7=nan:1;tick@3=latency"
                         ":0.01' (see repro.runtime.chaos); implies "
                         "--resilience")
    ap.add_argument("--deadline", type=int, default=None, metavar="TICKS",
                    help="per-request SLO deadline in driver ticks since "
                         "submit; expired requests are evicted, "
                         "infeasible ones shed")
    ap.add_argument("--snapshot-dir", default=None,
                    help="write periodic serving snapshots here "
                         "(resume a crashed workload with Server.resume)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="ticks between snapshots (with --snapshot-dir)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import mesh_from_spec
        mesh = mesh_from_spec(args.mesh)
    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    chaos = None
    if args.chaos:
        from repro.runtime.chaos import ChaosInjector, ChaosPlan
        chaos = ChaosInjector(ChaosPlan.parse(args.chaos))
    resilience = (ResilienceConfig()
                  if (args.resilience or chaos is not None) else None)
    srv = Server(args.arch, smoke=not args.full, slots=args.slots, mesh=mesh,
                 tracer=tracer, resilience=resilience, chaos=chaos,
                 snapshot_dir=args.snapshot_dir,
                 snapshot_every=args.snapshot_every)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, srv.cfg.vocab,
                                        rng.integers(2, 6)).tolist(),
                    max_new=args.max_new, deadline_ticks=args.deadline)
            for i in range(args.requests)]
    report = srv.run_workload(reqs, stagger_ticks=args.stagger)
    if args.check:
        got = {r.rid: r.out for r in srv.finished if r.status == "ok"}
        ref = sequential_reference(
            args.arch, [Request(rid=r.rid, prompt=list(r.prompt),
                                max_new=r.max_new) for r in reqs],
            slots=args.slots, smoke=not args.full, mesh=mesh)
        ok = all(got[rid] == ref[i]
                 for i, r in enumerate(reqs)
                 for rid in (r.rid,) if rid in got)
        report["identical_to_sequential"] = ok
        if not ok:
            raise SystemExit("continuous-batching outputs diverge from "
                             "sequential decode")
    if args.trace:
        tracer.write(args.trace)
        report["trace"] = args.trace
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
