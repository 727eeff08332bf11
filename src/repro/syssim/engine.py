"""Discrete-event multi-chain system simulator.

Each in-flight request is a :class:`ChainJob` — an ordered task list from
:func:`repro.syssim.route.route_chain` plus an arrival cycle. Tasks queue
FIFO at their routed unit (one task in service per unit); while a task is
in service its unit injects interconnect traffic at its average demand
rate, the shared :class:`~repro.syssim.interconnect.Interconnect`
arbitrates max-min fair shares each interval, and a task's progress
scales with its granted fraction of demand. Consequences:

  * one unit, one chain, ample capacity -> every rate is 1.0 and the
    makespan is exactly ``repro.sim.simulate_chain`` (handoff credits are
    honored when chain-adjacent tasks run back-to-back on one unit);
  * taking capacity away (or adding concurrent jobs) can only slow tasks
    down — latency is monotone under added contention — and every lost
    cycle is attributed (``queue`` vs ``interconnect`` stalls);
  * words are conserved: granted flow integrates to exactly the offered
    task traffic, never more, never less.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from .interconnect import Interconnect
from .route import RoutedChain, Task
from .stats import JobStats, SystemReport, UnitStats
from .system import SystemSpec

def _tol(scale: float) -> float:
    """The engine's one tolerance, scaled to the magnitude it guards: 1e-9
    up to 1, relative above (cycle counts reach 1e9, where a fixed 1e-9
    is below one ulp)."""
    return 1e-9 * max(1.0, abs(scale))


@dataclass
class ChainJob:
    """One request: a routed chain instance entering at ``arrival``."""

    routed: RoutedChain
    arrival: float = 0.0
    tokens: float = 1.0
    name: Optional[str] = None
    rid: Optional[int] = None

    @property
    def tasks(self) -> List[Task]:
        return self.routed.tasks


@dataclass
class _Running:
    job: int
    task_idx: int
    task: Task
    remaining: float
    work0: float                       # service cycles after handoff credit
    demand: float                      # words/cycle while in service


@dataclass
class _UnitState:
    stats: UnitStats
    link_bw: float
    running: Optional[_Running] = None
    queue: List[tuple] = field(default_factory=list)  # (ready, seq, job, ti)
    last_done: Optional[tuple] = None                 # (job, task_idx)


def _demand(task: Task, link_bw: float) -> float:
    """Words/cycle while in service. A rate within tolerance of zero is
    zero: the arbiter would grant it nothing, so the task would never
    progress. Its words are booked at retirement instead (``complete``)."""
    if task.work <= 0 or task.bus_words <= 0:
        return 0.0
    d = task.bus_words / task.work
    return 0.0 if d <= _tol(link_bw) else min(d, link_bw)


def simulate_system(jobs: Sequence[ChainJob],
                    system: SystemSpec) -> SystemReport:
    """Run ``jobs`` to completion on ``system``; returns the full report
    (per-unit utilization/stalls, interconnect accounting, per-job
    latency/energy, makespan)."""
    units: Dict[str, _UnitState] = {
        u.name: _UnitState(stats=UnitStats(name=u.name, kind=u.kind),
                           link_bw=u.link_bw)
        for u in system.units}
    ic = Interconnect(capacity=system.capacity)
    job_stats: List[JobStats] = [
        JobStats(name=j.name or j.routed.name, arrival=float(j.arrival),
                 finish=float(j.arrival), tokens=float(j.tokens),
                 rid=j.rid)
        for j in jobs]
    for i, j in enumerate(jobs):
        if j.arrival < 0:
            raise ValueError(f"job {i} has negative arrival {j.arrival}")
        for t in j.tasks:
            if t.unit not in units:
                raise KeyError(f"task {t.name} routed to unknown unit "
                               f"{t.unit!r}")

    arrivals = sorted(range(len(jobs)), key=lambda i: (jobs[i].arrival, i))
    next_arrival = 0
    seq = 0                       # FIFO tie-break for same-ready-time tasks
    now = 0.0
    handoff_applied = 0.0
    remaining_tasks = sum(len(j.tasks) for j in jobs)

    def enqueue(job_idx: int, task_idx: int, ready: float):
        nonlocal seq
        task = jobs[job_idx].tasks[task_idx]
        us = units[task.unit]
        us.queue.append((ready, seq, job_idx, task_idx))
        us.queue.sort()
        seq += 1

    def complete(us: _UnitState, r: _Running):
        nonlocal remaining_tasks
        st = us.stats
        st.tasks += 1
        st.compute_cycles += r.task.compute
        st.offered_words += r.task.bus_words
        st.energy += r.task.energy
        # conservation true-up: the fluid flow integrates demand over the
        # *credited* service window; the words hidden under the handoff
        # overlap, those of a negligible demand (``_demand``) and any fp
        # residue still crossed the interconnect — book them at
        # retirement so injected == offered exactly
        shortfall = r.task.bus_words - r.demand * r.work0
        if shortfall > 0.0:
            st.injected_words += shortfall
            ic.injected[us.stats.name] = (
                ic.injected.get(us.stats.name, 0.0) + shortfall)
            ic.forwarded_words += shortfall
        us.last_done = (r.job, r.task_idx)
        us.running = None
        remaining_tasks -= 1
        nxt = r.task_idx + 1
        if nxt < len(jobs[r.job].tasks):
            enqueue(r.job, nxt, now)
        else:
            job_stats[r.job].finish = now
            job_stats[r.job].energy = jobs[r.job].routed.energy

    def start_ready():
        """Move queued tasks into service; zero-work tasks retire
        immediately (possibly unblocking their successor on this unit)."""
        nonlocal handoff_applied
        progressed = True
        while progressed:
            progressed = False
            for us in units.values():
                if us.running is not None or not us.queue:
                    continue
                ready, _, job_idx, task_idx = us.queue[0]
                if ready > now + _tol(now):
                    continue
                us.queue.pop(0)
                task = jobs[job_idx].tasks[task_idx]
                us.stats.queue_cycles += max(0.0, now - ready)
                work = task.work
                if (task.handoff_credit > 0.0
                        and us.last_done == (job_idx, task_idx - 1)):
                    credit = min(task.handoff_credit, work)
                    work -= credit
                    handoff_applied += credit
                us.running = _Running(job=job_idx, task_idx=task_idx,
                                      task=task, remaining=work, work0=work,
                                      demand=_demand(task, us.link_bw))
                progressed = True
                if work <= _tol(task.work):
                    complete(us, us.running)

    # admit nothing yet; the loop advances time across arrivals,
    # completions and arbitration changes
    max_steps = 1000 * max(1, remaining_tasks) + 1000
    steps = 0
    while remaining_tasks > 0:
        steps += 1
        if steps > max_steps:
            raise RuntimeError("syssim: event-loop failed to converge "
                               f"({remaining_tasks} tasks stranded)")
        while (next_arrival < len(arrivals)
               and jobs[arrivals[next_arrival]].arrival <= now + _tol(now)):
            enqueue(arrivals[next_arrival], 0,
                    jobs[arrivals[next_arrival]].arrival)
            next_arrival += 1
        start_ready()
        active = {n: us for n, us in units.items() if us.running is not None}
        if not active:
            if next_arrival < len(arrivals):
                now = max(now, jobs[arrivals[next_arrival]].arrival)
                continue
            # tasks queued in the future only (handoff of ready times)
            pending = [q[0] for us in units.values() for q in us.queue]
            if not pending:
                break
            now = max(now, min(pending))
            continue

        demands = {n: us.running.demand for n, us in active.items()}
        alloc = ic.allocate(demands)
        rates = {}
        for n, us in active.items():
            d = demands[n]
            rates[n] = 1.0 if d <= 0 else min(1.0, alloc[n] / d)

        dt = min(us.running.remaining / max(rates[n], 1e-30)
                 for n, us in active.items())
        if next_arrival < len(arrivals):
            dt = min(dt, jobs[arrivals[next_arrival]].arrival - now)
        dt = max(dt, 0.0)

        flows = {}
        for n, us in active.items():
            r = rates[n]
            us.running.remaining -= r * dt
            us.stats.busy_cycles += dt
            us.stats.contention_stall_cycles += (1.0 - r) * dt
            w = demands[n] * r
            if w > 0:
                flows[n] = w
                us.stats.injected_words += w * dt
        ic.advance(flows, dt, sum(demands.values()))
        now += dt

        for n, us in list(active.items()):
            if (us.running is not None
                    and us.running.remaining <= _tol(us.running.work0)):
                complete(us, us.running)

    makespan = max([now] + [j.finish for j in job_stats]) if job_stats \
        else now
    return SystemReport(system=system.name,
                        units=[us.stats for us in units.values()],
                        jobs=job_stats, interconnect=ic,
                        makespan=makespan,
                        handoff_overlap_cycles=handoff_applied)
