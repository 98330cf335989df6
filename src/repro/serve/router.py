"""Fault-tolerant multi-replica request router over ``ContinuousEngine``.

This is the serving-side execution of the planner's ``replicas`` axis:
``HybridPlanner.best_inference`` picks a (replicas x tp, slots) layout and
``ReplicaRouter.from_choice`` instantiates it — N independent continuous-
batching engine groups, each on its own tp-device mesh, behind one
admission front door with least-loaded dispatch.  Robustness is the point:
at the scale where multi-group layouts win, replica failure is the norm,
and a replica dying must not lose its in-flight requests.

Failover state machine
======================

Per replica::

    healthy --kill fault/process loss--------------> dead
    healthy --watchdog timeout (stall)-------------> degraded
    healthy --non-finite logprob (nanlogits)-------> degraded
    healthy --drain_replica()----------------------> draining --empty--> removed

- **healthy**: dispatchable, stepped every router tick.  Health is
  observed, not assumed: each engine step runs inside an armed
  ``train.fault.Watchdog`` (tick-progress heartbeat), and every logprob
  the replica emits is checked for NaN/Inf.
- **dead**: the engine is gone (simulated SIGKILL).  Its state is
  unreachable — recovery uses only the ROUTER-side streaming records
  (progress through the replica's last completed tick).
- **degraded**: the engine object still exists but is quarantined — a
  replica that hangs past the watchdog or emits non-finite logits cannot
  be trusted with further work.  Its requests are harvested exactly like
  a dead replica's (for nanlogit faults the generated suffix from the
  first non-finite logprob onward is discarded — those tokens came from
  poisoned math).
- **draining/removed**: elastic shrink, mirroring PR 7's elastic DP —
  no new dispatch, in-flight work finishes, then the replica is removed.
  ``add_replica()`` is the matching grow.

Per request::

    submitted --dispatch--> on replica r --finish--> result (exactly once)
        |                        |
        | projected wait >       | replica dead/degraded
        |   deadline             v
        +--> shed            retry wait (capped exponential backoff)
                                 |  deadline-aware: a retry that cannot
                                 |  start before the deadline times out
                                 v
                             re-dispatched with replay_tokens

Failover re-dispatch is **bit-identical** to an unfaulted run: every
replica engine shares the same base seed, sampling keys are (rid, n_gen)-
addressed (independent of batch/replica placement), and the new replica
re-prefills the prompt exactly as a fresh run would, then REPLAYS the
already-generated tokens through the same decode ticks that produced them
(see ``Request.replay_tokens``) — reconstructing the original computation
op for op instead of re-prefilling prompt+generated in one shot (which
would reorder attention reductions and drift in the last bits).

Fault injection reuses the ``train.fault`` schedule grammar, replica-keyed:
``kill@N:R`` (replica R dies before router tick N), ``stall@N:R:SECS``
(replica R hangs inside tick N; the watchdog flags it), ``nanlogits@N:R``
(replica R's tick N emits NaN logprobs).  Like training faults, a fault at
tick N fires when tick N is *about to run*, so schedules are reproducible.

Load shedding: admission is bounded twice — per-engine ``max_queue``
(hard bound on queued requests) and, for deadline-carrying requests, a
projected-wait check: ``backlog_tokens x EWMA(step seconds)`` on the
least-loaded replica; if that already overshoots the deadline the request
is shed at the door (``finished_reason="shed"``) instead of timing out
after consuming resources.  Every submitted rid lands in ``results``
exactly once — completed, shed, or timed out.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.serve.continuous import ContinuousEngine, Request, RequestResult
from repro.train.fault import Fault, Watchdog

REPLICA_FAULT_KINDS = ("kill", "stall", "nanlogits")


@dataclasses.dataclass
class _Replica:
    idx: int
    engine: Optional[ContinuousEngine]
    state: str = "healthy"   # healthy|degraded|dead|draining|removed
    stalled: bool = False    # set by the watchdog thread, read post-step

    @property
    def live(self) -> bool:
        return self.state in ("healthy", "draining")


@dataclasses.dataclass
class _Tracked:
    """Router-side streaming record for one in-flight rid: the original
    request plus progress mirrored after every completed replica tick —
    the only thing failover from a DEAD replica can recover from."""
    req: Request
    replica: Optional[int]           # None while waiting for a retry slot
    tokens: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    failovers: int = 0
    ready_at: float = 0.0            # retry backoff gate (absolute)
    deadline: Optional[float] = None  # absolute; None = no deadline


def replica_meshes(replicas: int, tp: int) -> List[jax.sharding.Mesh]:
    """One ``("data", "model")`` = (1, tp) mesh per replica on disjoint
    devices: replica ``r`` takes devices ``[r*tp, (r+1)*tp)``."""
    devs = jax.devices()
    need = replicas * tp
    if need > len(devs):
        raise ValueError(f"{replicas} replicas x tp {tp} need {need} "
                         f"devices, only {len(devs)} visible")
    return [jax.sharding.Mesh(np.asarray(devs[r * tp:(r + 1) * tp]
                                         ).reshape(1, tp), ("data", "model"))
            for r in range(replicas)]


def _valid_prefix(tokens: Sequence[int], logprobs: Sequence[float]):
    """Progress up to (excluding) the first non-finite logprob: everything
    from poisoned math onward is untrusted and must be regenerated."""
    for i, lp in enumerate(logprobs):
        if not math.isfinite(lp):
            return list(tokens[:i]), list(logprobs[:i])
    return list(tokens), list(logprobs)


class ReplicaRouter:
    """See module docstring.  ``faults`` takes replica-keyed ``Fault``s
    (``train.fault.parse_fault_schedule`` forms ``kill@N:R`` /
    ``stall@N:R:SECS`` / ``nanlogits@N:R``); training-form faults (no
    replica) are rejected.  ``clock``/``sleep_fn`` are injectable for
    deterministic tests; the watchdog and injected stalls use real time
    (the watchdog is a timer thread)."""

    def __init__(self, api, params, *, replicas: int, n_slots: int,
                 capacity: int, prefill_chunk: int = 0,
                 temperature: float = 0.0, seed: int = 0,
                 meshes: Optional[Sequence] = None,
                 model_axis: Optional[str] = None, batch_axes=(),
                 comm_chunks: int = 1, window=None,
                 context_axis: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 faults: Sequence[Fault] = (),
                 watchdog_timeout_s: Optional[float] = None,
                 watchdog_warmup_ticks: int = 2,
                 retry_backoff_s: float = 0.05,
                 max_retry_backoff_s: float = 1.0,
                 est_step_s: float = 0.0,
                 clock: Callable[[], float] = time.monotonic,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 log_fn: Callable[[str], None] = lambda m: None):
        if replicas < 1:
            raise ValueError(f"need at least one replica, got {replicas}")
        if meshes is not None and len(meshes) != replicas:
            raise ValueError(f"{len(meshes)} meshes for {replicas} replicas")
        for f in faults:
            if f.kind not in REPLICA_FAULT_KINDS or f.replica is None:
                raise ValueError(
                    f"router faults must be replica-keyed "
                    f"{REPLICA_FAULT_KINDS} (kind@tick:replica...), got "
                    f"{f.kind}@{f.step} with replica={f.replica}")
        self._api, self._params = api, params
        self._engine_kw = dict(
            n_slots=n_slots, capacity=capacity, prefill_chunk=prefill_chunk,
            temperature=temperature, seed=seed, model_axis=model_axis,
            batch_axes=batch_axes, comm_chunks=comm_chunks, window=window,
            context_axis=context_axis, max_queue=max_queue, clock=clock)
        self._meshes = list(meshes) if meshes is not None else None
        self.replicas: List[_Replica] = []
        for r in range(replicas):
            self.replicas.append(_Replica(r, self._make_engine(r)))
        self.faults = [dataclasses.replace(f) for f in faults]
        self.fault_log: List[tuple] = []     # (kind, tick, replica)
        self._clock, self._sleep, self._log = clock, sleep_fn, log_fn
        self._watchdog = (Watchdog(watchdog_timeout_s, self._on_stall)
                          if watchdog_timeout_s is not None else None)
        # the first steps JIT-compile the prefill/decode functions (seconds,
        # vs milliseconds once warm) — arming the heartbeat there would flag
        # compilation as a stall on every replica
        self._watchdog_warmup = watchdog_warmup_ticks
        self.retry_backoff_s = retry_backoff_s
        self.max_retry_backoff_s = max_retry_backoff_s
        self._est_step_s = est_step_s        # EWMA seconds per engine step
        self.ticks = 0
        self.tracked: Dict[int, _Tracked] = {}
        self.results: List[RequestResult] = []
        self.stats = {"completed": 0, "shed": 0, "timed_out": 0,
                      "failovers": 0}

    def _make_engine(self, idx: int) -> ContinuousEngine:
        mesh = self._meshes[idx] if self._meshes is not None else None
        return ContinuousEngine(self._api, self._params, mesh=mesh,
                                **self._engine_kw)

    @classmethod
    def from_choice(cls, api, params, choice, *, capacity: int, **kw):
        """Build the router an ``InferenceChoice`` plans: ``choice.replicas``
        engine groups of ``choice.tp`` devices each (``replica_meshes``:
        disjoint device subsets, tensor-parallel inside the group when
        tp > 1) with ``choice.slots`` request lanes per group."""
        meshes = replica_meshes(choice.replicas, choice.tp)
        model_axis, batch_axes = (("model", ("data",)) if choice.tp > 1
                                  else (None, ()))
        return cls(api, params, replicas=choice.replicas,
                   n_slots=choice.slots, capacity=capacity, meshes=meshes,
                   model_axis=model_axis, batch_axes=batch_axes, **kw)

    # -- health ---------------------------------------------------------------

    def _on_stall(self, idx: int) -> None:
        self.replicas[idx].stalled = True

    @property
    def replica_states(self) -> List[str]:
        return [r.state for r in self.replicas]

    def _healthy(self) -> List[_Replica]:
        return [r for r in self.replicas if r.state == "healthy"]

    # -- admission ------------------------------------------------------------

    def submit(self, req: Request) -> Optional[RequestResult]:
        """Admit ``req``.  Returns ``None`` on acceptance or the shaped
        shed/timeout result on rejection; duplicate in-flight rids raise
        (same contract as ``ContinuousEngine.submit``)."""
        if req.rid in self.tracked:
            raise ValueError(
                f"request {req.rid}: a request with rid {req.rid} is "
                f"already in flight on the router")
        now = self._clock()
        tr = _Tracked(req=req, replica=None,
                      deadline=(now + req.deadline_s
                                if req.deadline_s is not None else None))
        self.tracked[req.rid] = tr
        try:
            return self._dispatch(tr, now)
        except Exception:
            del self.tracked[req.rid]        # invalid request never tracked
            raise

    def _backlog_tokens(self, rep: _Replica) -> int:
        eng = rep.engine
        return (sum(r.max_new_tokens for r in eng.queue)
                + sum(st.req.max_new_tokens - st.n_gen
                      for st in eng.active.values()))

    def _dispatch(self, tr: _Tracked, now: float):
        """Least-loaded dispatch with projected-wait shedding.  Returns the
        shaped result on shed/timeout, else None."""
        cands = self._healthy()
        if not cands:
            if any(r.state == "draining" for r in self.replicas):
                # shrink in progress: hold in the retry queue until the
                # drain finishes or the deadline expires
                tr.replica, tr.ready_at = None, now
                return None
            return self._finalize(tr, "shed")
        rep = min(cands, key=lambda r: (len(r.engine.queue)
                                        + len(r.engine.active), r.idx))
        if tr.deadline is not None:
            remaining = tr.deadline - now
            if remaining <= 0:
                return self._finalize(tr, "timed_out")
            projected = self._backlog_tokens(rep) * self._est_step_s
            if projected > remaining:
                self._log(f"[router] shed rid={tr.req.rid}: projected wait "
                          f"{projected:.3f}s > deadline {remaining:.3f}s")
                return self._finalize(tr, "shed")
        req = dataclasses.replace(
            tr.req, replay_tokens=tuple(tr.tokens),
            replay_logprobs=tuple(tr.logprobs),
            deadline_s=(tr.deadline - now
                        if tr.deadline is not None else None))
        res = rep.engine.submit(req)
        if res is not None:                  # engine max_queue shed
            rep.engine.results.pop()         # router owns the accounting
            return self._finalize(tr, "shed")
        tr.replica = rep.idx
        return None

    def _finalize(self, tr: _Tracked, reason: str,
                  res: Optional[RequestResult] = None) -> RequestResult:
        if res is None:
            res = RequestResult(rid=tr.req.rid,
                                prompt_len=len(tr.req.tokens),
                                tokens=list(tr.tokens),
                                logprobs=list(tr.logprobs),
                                finished_reason=reason)
        self.results.append(res)
        self.stats["completed" if reason in ("eos", "length")
                   else reason] += 1
        del self.tracked[tr.req.rid]
        return res

    # -- failover -------------------------------------------------------------

    def _failover(self, tr: _Tracked, now: float) -> None:
        """Replica loss: keep the trusted progress prefix, park the request
        behind a capped exponential backoff, deadline-aware."""
        tr.tokens, tr.logprobs = _valid_prefix(tr.tokens, tr.logprobs)
        tr.replica = None
        tr.failovers += 1
        self.stats["failovers"] += 1
        backoff = min(self.retry_backoff_s * (2 ** (tr.failovers - 1)),
                      self.max_retry_backoff_s)
        tr.ready_at = now + backoff
        if tr.deadline is not None and tr.ready_at >= tr.deadline:
            self._finalize(tr, "timed_out")  # retry could never finish
            return
        self._log(f"[router] failover rid={tr.req.rid} "
                  f"({len(tr.tokens)} tokens kept, retry in {backoff:.3f}s)")

    def _harvest(self, rep: _Replica, now: float) -> None:
        """Pull every request assigned to ``rep`` back into the retry
        queue.  Uses the ROUTER-side records — a dead replica's engine
        state is unreachable by definition."""
        for tr in [t for t in self.tracked.values()
                   if t.replica == rep.idx]:
            self._failover(tr, now)

    def drain_replica(self, idx: int) -> None:
        """Elastic shrink: stop dispatching to replica ``idx``; its
        in-flight work finishes, then it is removed."""
        rep = self.replicas[idx]
        if rep.state == "healthy":
            rep.state = "draining"

    def add_replica(self) -> int:
        """Elastic grow: append a fresh healthy replica (same engine
        geometry; same seed, so failover onto it stays bit-identical)."""
        if self._meshes is not None:
            raise ValueError("add_replica with explicit meshes: provide the "
                             "new replica's device group via meshes instead")
        idx = len(self.replicas)
        self.replicas.append(_Replica(idx, self._make_engine(idx)))
        return idx

    # -- one router tick ------------------------------------------------------

    def _pending_faults(self, kind: str, tick: int, idx: int) -> List[Fault]:
        return [f for f in self.faults if f.kind == kind and f.step == tick
                and f.replica == idx and f.times > 0]

    def step(self) -> bool:
        """One router tick: fire scheduled faults, re-dispatch ready
        retries, step every live replica under the watchdog, mirror
        progress, collect results, quarantine unhealthy replicas.
        Returns True while any request is in flight."""
        tick = self.ticks + 1
        now = self._clock()

        # (1) re-dispatch retries whose backoff has elapsed
        for tr in list(self.tracked.values()):
            if tr.replica is None:
                if tr.deadline is not None and now >= tr.deadline:
                    self._finalize(tr, "timed_out")
                elif now >= tr.ready_at:
                    self._dispatch(tr, now)

        for rep in self.replicas:
            if not rep.live:
                continue
            # (2) scheduled faults fire when tick N is about to run
            killed = False
            for f in self._pending_faults("kill", tick, rep.idx):
                f.times = 0
                killed = True
            if killed:
                self.fault_log.append(("kill", tick, rep.idx))
                self._log(f"[router] replica {rep.idx} killed before "
                          f"tick {tick}")
                rep.state, rep.engine = "dead", None
                self._harvest(rep, now)
                continue
            for f in self._pending_faults("nanlogits", tick, rep.idx):
                f.times = 0
                self.fault_log.append(("nanlogits", tick, rep.idx))
                rep.engine.poison_decode_ticks(1)
            stall_s = 0.0
            for f in self._pending_faults("stall", tick, rep.idx):
                f.times = 0
                self.fault_log.append(("stall", tick, rep.idx))
                stall_s += f.seconds

            # (3) one engine step under the armed watchdog heartbeat
            armed = (self._watchdog is not None
                     and self.ticks >= self._watchdog_warmup)
            if armed:
                self._watchdog.arm(rep.idx)
            if stall_s > 0.0:
                self._sleep(stall_s)         # hang INSIDE the armed window
            t0 = self._clock()
            rep.engine.step()
            dt = self._clock() - t0 + stall_s
            if armed:
                self._watchdog.disarm()
            self._est_step_s = (dt if self._est_step_s <= 0.0
                                else 0.8 * self._est_step_s + 0.2 * dt)

            # (4) mirror per-rid progress (streaming records: what failover
            # from a dead replica recovers) and scan logprobs for poison
            poisoned = False
            for st in rep.engine.active.values():
                tr = self.tracked.get(st.req.rid)
                if tr is not None:
                    tr.tokens = list(st.tokens)
                    tr.logprobs = list(st.logprobs)
                    if st.logprobs and not math.isfinite(st.logprobs[-1]):
                        poisoned = True

            # (5) collect finished results; poisoned ones are NOT delivered
            for res in rep.engine.results:
                tr = self.tracked.get(res.rid)
                if tr is None:
                    continue                 # already accounted (defensive)
                if any(not math.isfinite(lp) for lp in res.logprobs):
                    poisoned = True
                    tr.tokens, tr.logprobs = _valid_prefix(res.tokens,
                                                           res.logprobs)
                else:
                    self._finalize(tr, res.finished_reason, res)
            rep.engine.results.clear()

            if rep.stalled or poisoned:
                why = "stalled past watchdog" if rep.stalled else "NaN/Inf logits"
                self._log(f"[router] replica {rep.idx} degraded ({why})")
                rep.state = "degraded"
                self._harvest(rep, now)
            elif rep.state == "draining" and not (rep.engine.active
                                                  or rep.engine.queue):
                rep.state, rep.engine = "removed", None

        self.ticks = tick
        if self.tracked and not any(r.live for r in self.replicas):
            raise RuntimeError(
                f"{len(self.tracked)} request(s) in flight but no live "
                f"replica remains (states: {self.replica_states})")
        return bool(self.tracked)

    def run(self, requests: Sequence[Request]) -> List[RequestResult]:
        """Submit everything, step until every rid has a result (exactly
        one per submitted rid), return results ordered by rid."""
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        return sorted(self.results, key=lambda r: r.rid)

    def close(self) -> None:
        if self._watchdog is not None:
            self._watchdog.close()
