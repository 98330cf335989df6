r"""Continuous-batching serving engine over a slotted KV cache.

Slot/admission model
====================

The engine owns ONE slotted cache (``models.api.make_slot_cache``):
``n_slots`` independent request lanes, each a linear KV region of
``capacity`` positions with its own write position (``cache["pos"]`` is
(n_slots,)).  Requests flow through three states:

    queued --admit--> prefilling --last chunk--> decoding --eos/budget--> done
                       (slot held)                (slot held)            (slot freed)
         \________________________ deadline_s ________________________/
          an expired request exits from ANY state at the next step() —
          slot freed, partial tokens returned flagged "timed_out"

Per ``step()`` the engine (1) **admits** queued requests into free slots,
(2) runs ONE prefill chunk for the head-of-line prefilling request —
chunked prefill is what keeps a long prompt from stalling the running
batch: decode ticks interleave between its chunks, (3) runs ONE decode
tick over ALL slots with an active-row mask, (4) **evicts** finished
requests (EOS or token budget) and frees their slots for the next
admission.  Everything the device sees is fixed-shape — admission and
eviction only edit slot rows and the mask, so joining requests never
retrace the jitted tick and (pinned by test) never perturb the tokens of
requests already in flight.

The decode tick comes from ``train.steps.make_continuous_steps``: under a
dp x tp mesh it executes ``transformer.decode_slots_tp`` — the whole layer
stack inside one shard_map with every Megatron matmul on the chunked
collective-matmul ppermute rings of ``parallel.collectives`` (no monolithic
all-gather / all-reduce in the compiled decode HLO).  The prefill chunk
shards the same way (``prefill_chunk_tp``: chunk sequence dim in the
ring-row role), or — with ``context_axis`` — context-parallel on the
ppermute KV ring (``prefill_chunk_cp``, ``parallel.context``).

Sampling keys fold ``(request id, tokens generated)`` into the engine seed,
so a request's random stream is independent of which other requests share
its batch — this is what makes mid-flight joins bit-reproducible.

Which (replicas x tp, slots) to deploy is the latency-SLO-constrained
search ``core.planner.HybridPlanner.best_inference``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.api import (ModelApi, cache_evict_slot, make_slot_cache)
from repro.train.steps import make_continuous_steps


@dataclasses.dataclass
class Request:
    rid: int
    tokens: Sequence[int]            # prompt token ids
    max_new_tokens: int
    eos_id: Optional[int] = None
    # TTL in seconds from submit().  Once expired the request is evicted —
    # queued or mid-flight — its slot freed, and its result returned with
    # finished_reason="timed_out" and whatever tokens were generated.  One
    # stalled long request can therefore never starve admission forever.
    deadline_s: Optional[float] = None
    # Failover resume (``serve.router``): tokens this request already
    # generated on a replica that died mid-flight, plus their logprobs.  The
    # engine prefills the prompt exactly as a fresh run would, then REPLAYS
    # these tokens through the same decode ticks that produced them (forced
    # instead of sampled) — reconstructing the unfaulted computation op for
    # op, so the continuation's tokens/logprobs are bit-identical to a run
    # that never failed over.  (A one-shot re-prefill of prompt + generated
    # would reorder the attention reductions and drift in the last bits.)
    replay_tokens: Sequence[int] = ()
    replay_logprobs: Sequence[float] = ()


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: List[int]                # generated ids (stop token included)
    logprobs: List[float]
    finished_reason: str             # "eos" | "length" | "timed_out" | "shed"


@dataclasses.dataclass
class _Active:
    req: Request
    slot: int
    consumed: int = 0                # prompt tokens prefilled so far
    n_gen: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    last_logits: Optional[jnp.ndarray] = None   # set once prefill completes

    @property
    def decoding(self) -> bool:
        return self.last_logits is not None


class ContinuousEngine:
    """See module docstring.  ``prefill_chunk=0`` prefills each prompt in
    one shot (still interleaved with decode ticks); > 0 caps the tokens per
    prefill step.  ``mesh``/``model_axis``/``batch_axes`` route the decode
    tick onto the collective-ring TP step when the arch and slot count
    divide (``transformer.decode_slots_tp_supported``) and the prefill
    chunk onto ``prefill_chunk_tp`` (same rings, the chunk's sequence dim
    in the ring-row role).  ``context_axis`` instead routes the prefill
    chunk onto the sequence-sharded KV ring (``prefill_chunk_cp``)."""

    def __init__(self, api: ModelApi, params, *, n_slots: int, capacity: int,
                 prefill_chunk: int = 0, temperature: float = 0.0,
                 seed: int = 0, mesh=None, model_axis: Optional[str] = None,
                 batch_axes=(), comm_chunks: int = 1, window=None,
                 context_axis: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.api = api
        self.params = params
        self.n_slots = n_slots
        self.capacity = capacity
        self.prefill_chunk = prefill_chunk
        self.temperature = temperature
        self.max_queue = max_queue    # bound on queued (not-yet-admitted) reqs
        self._clock = clock           # injectable for deterministic TTL tests
        self._deadline: Dict[int, float] = {}    # rid -> absolute deadline
        self._base_key = jax.random.PRNGKey(seed)
        self.cache = make_slot_cache(api.cfg, n_slots, capacity)
        if mesh is not None and mesh.size == 1:
            # a one-device mesh pins the engine (a one-chip replica) to its
            # device: the jitted steps follow their committed inputs there
            dev = NamedSharding(mesh, P())
            self.params = jax.device_put(params, dev)
            self.cache = jax.device_put(self.cache, dev)
        (self._decode_tick, self._prefill_chunk,
         self._prefill_grid) = make_continuous_steps(
            api, n_slots=n_slots, temperature=temperature, mesh=mesh,
            model_axis=model_axis, batch_axes=batch_axes,
            comm_chunks=comm_chunks, window=window,
            context_axis=context_axis)
        self.queue: List[Request] = []
        self.active: Dict[int, _Active] = {}       # slot -> state
        self.results: List[RequestResult] = []
        self.ticks = 0                # completed step() count (heartbeat)
        self._poison_ticks = 0        # fault hook: decode ticks to NaN out

    # -- request lifecycle ---------------------------------------------------

    def submit(self, req: Request) -> Optional[RequestResult]:
        """Enqueue ``req``.  Returns ``None`` on acceptance; when
        ``max_queue`` is set and the queue is full, the request is REJECTED
        with a shaped ``RequestResult(finished_reason="shed")`` (appended to
        ``results`` and returned) instead of growing the queue without
        bound.  A rid already in flight raises: deadlines and results are
        rid-keyed, so a duplicate would silently overwrite the first
        request's deadline and corrupt its accounting."""
        in_flight = ({r.rid for r in self.queue}
                     | {st.req.rid for st in self.active.values()})
        if req.rid in in_flight:
            raise ValueError(
                f"request {req.rid}: a request with rid {req.rid} is already "
                f"in flight (queued or holding a slot) — rids key deadlines "
                f"and results, so submit each rid at most once until its "
                f"result is returned")
        n = len(req.tokens)
        if n + req.max_new_tokens > self.capacity:
            raise ValueError(
                f"request {req.rid}: prompt ({n}) + max_new_tokens "
                f"({req.max_new_tokens}) = {n + req.max_new_tokens} exceeds "
                f"slot capacity {self.capacity}")
        if len(req.replay_tokens) != len(req.replay_logprobs):
            raise ValueError(
                f"request {req.rid}: {len(req.replay_tokens)} replay tokens "
                f"but {len(req.replay_logprobs)} replay logprobs — the "
                f"failover resume needs one logprob per replayed token")
        if len(req.replay_tokens) > req.max_new_tokens:
            raise ValueError(
                f"request {req.rid}: {len(req.replay_tokens)} replay tokens "
                f"exceed max_new_tokens ({req.max_new_tokens})")
        if self._prefill_grid > 1:
            # sharded prefill pads the final chunk up to the ring grid; the
            # padded rows must still land inside the slot's linear region
            t_f = (n if self.prefill_chunk <= 0
                   else (n % self.prefill_chunk or self.prefill_chunk))
            pad = -t_f % self._prefill_grid
            if n + pad > self.capacity:
                raise ValueError(
                    f"request {req.rid}: prompt ({n}) + sharded-prefill pad "
                    f"({pad}, grid {self._prefill_grid}) exceeds slot "
                    f"capacity {self.capacity} — grow capacity by the pad "
                    f"slack or align the prompt to the chunk grid")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            res = RequestResult(rid=req.rid, prompt_len=n, tokens=[],
                                logprobs=[], finished_reason="shed")
            self.results.append(res)
            return res
        if req.deadline_s is not None:
            self._deadline[req.rid] = self._clock() + req.deadline_s
        self.queue.append(req)
        return None

    def take_queued(self) -> List[Request]:
        """Remove and return every not-yet-admitted request — the router's
        drain/failover hook (queued requests hold no slot state, so they can
        re-dispatch to another replica as-is)."""
        out, self.queue = self.queue, []
        for r in out:
            self._deadline.pop(r.rid, None)
        return out

    def poison_decode_ticks(self, n: int = 1) -> None:
        """Fault hook (``serve.router`` nanlogits injection): the next ``n``
        decode ticks return NaN logprobs (and token 0) for every live row,
        emulating a replica whose math went bad (ECC fault, bad reduction).
        Consumed only by ticks that actually decode."""
        self._poison_ticks += n

    def _expire(self):
        """Evict every request past its deadline — mid-flight requests free
        their slot (partial tokens returned), queued requests never admit."""
        now = self._clock()
        for st in list(self.active.values()):
            dl = self._deadline.get(st.req.rid)
            if dl is not None and now >= dl:
                self._finish(st, "timed_out")
        kept = []
        for req in self.queue:
            dl = self._deadline.get(req.rid)
            if dl is not None and now >= dl:
                self._deadline.pop(req.rid, None)
                self.results.append(RequestResult(
                    rid=req.rid, prompt_len=len(req.tokens), tokens=[],
                    logprobs=[], finished_reason="timed_out"))
            else:
                kept.append(req)
        self.queue = kept

    def _admit(self):
        free = [s for s in range(self.n_slots) if s not in self.active]
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.pop(0)
            self.cache = cache_evict_slot(self.cache, slot)
            self.active[slot] = _Active(req=req, slot=slot)

    def _finish(self, st: _Active, reason: str):
        self._deadline.pop(st.req.rid, None)
        self.results.append(RequestResult(
            rid=st.req.rid, prompt_len=len(st.req.tokens),
            tokens=st.tokens, logprobs=st.logprobs, finished_reason=reason))
        del self.active[st.slot]

    # -- one scheduler step --------------------------------------------------

    def _request_key(self, st: _Active):
        # (rid, n_gen)-addressed stream: independent of batch composition
        return jax.random.fold_in(
            jax.random.fold_in(self._base_key, st.req.rid), st.n_gen)

    def _sample_from(self, st: _Active):
        """Sample st's next token from its held last-position logits (host
        path used at the prefill->decode transition; decode-tick sampling
        happens inside the jitted tick with the same key schedule)."""
        lg = st.last_logits.astype(jnp.float32)
        if self.temperature <= 0.0:
            nxt = int(lg.argmax(-1))
        else:
            nxt = int(jax.random.categorical(
                self._request_key(st), lg / self.temperature))
        lp = float(jax.nn.log_softmax(lg, -1)[nxt])
        return nxt, lp

    def step(self) -> bool:
        """Expire / admit / one prefill chunk / one decode tick / evict.
        Returns True while any work remains."""
        self._expire()     # before admit: a freed slot admits THIS step
        self._admit()

        # (2) one prefill chunk for the head-of-line prefilling request
        pre = next((st for st in self.active.values() if not st.decoding),
                   None)
        if pre is not None:
            prompt = jnp.asarray(pre.req.tokens, jnp.int32)
            n = len(pre.req.tokens)
            chunk = (n - pre.consumed if self.prefill_chunk <= 0
                     else min(self.prefill_chunk, n - pre.consumed))
            toks = prompt[pre.consumed:pre.consumed + chunk][None]
            self.cache, last = self._prefill_chunk(
                self.params, self.cache, toks, pre.slot)
            pre.consumed += chunk
            if pre.consumed == n:
                pre.last_logits = last[0]        # prefill done -> decoding

        # (3) one decode tick over every decoding slot
        deco = [st for st in self.active.values() if st.decoding]
        if deco:
            tokens = jnp.zeros((self.n_slots,), jnp.int32)
            active = jnp.zeros((self.n_slots,), bool)
            keys = jnp.zeros((self.n_slots, 2), jnp.uint32)
            for st in deco:
                # the token a decode tick consumes is sampled from the
                # PREVIOUS position's logits: held host-side at the
                # prefill->decode seam, in-tick afterwards.  A failover
                # resume splices its recorded token instead of sampling.
                if not st.tokens:
                    if st.req.replay_tokens:
                        st.tokens.append(int(st.req.replay_tokens[0]))
                        st.logprobs.append(float(st.req.replay_logprobs[0]))
                    else:
                        nxt, lp = self._sample_from(st)
                        st.tokens.append(nxt)
                        st.logprobs.append(lp)
                    st.n_gen += 1
            live = [st for st in deco
                    if not self._hit_stop(st)
                    and st.n_gen < st.req.max_new_tokens]
            for st in live:
                tokens = tokens.at[st.slot].set(st.tokens[-1])
                active = active.at[st.slot].set(True)
                keys = keys.at[st.slot].set(
                    jnp.asarray(self._request_key(st), jnp.uint32))
            if live:
                self.cache, nxt, lp = self._decode_tick(
                    self.params, self.cache, tokens, active, keys)
                nxt, lp = jax.device_get((nxt, lp))
                poisoned = self._poison_ticks > 0
                if poisoned:
                    self._poison_ticks -= 1
                for st in live:
                    k = st.n_gen
                    if k < len(st.req.replay_tokens):
                        # replay: the tick ran (extending the cache exactly
                        # as the original decode did) but the output is the
                        # recorded token, not a fresh sample
                        st.tokens.append(int(st.req.replay_tokens[k]))
                        st.logprobs.append(float(st.req.replay_logprobs[k]))
                    elif poisoned:
                        st.tokens.append(0)
                        st.logprobs.append(float("nan"))
                    else:
                        st.tokens.append(int(nxt[st.slot]))
                        st.logprobs.append(float(lp[st.slot]))
                    st.n_gen += 1

        # (4) evict finished requests, freeing slots for the next admit
        for st in list(self.active.values()):
            if not st.decoding:
                continue
            if self._hit_stop(st):
                self._finish(st, "eos")
            elif st.n_gen >= st.req.max_new_tokens:
                st.tokens = st.tokens[:st.req.max_new_tokens]
                st.logprobs = st.logprobs[:st.req.max_new_tokens]
                self._finish(st, "length")
        self.ticks += 1            # progress heartbeat (router health checks)
        return bool(self.active or self.queue)

    def _hit_stop(self, st: _Active) -> bool:
        return (st.req.eos_id is not None and st.tokens
                and st.tokens[-1] == st.req.eos_id)

    def run(self, requests: Sequence[Request]) -> List[RequestResult]:
        """Submit everything, step until drained, return results by rid."""
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        return sorted(self.results, key=lambda r: r.rid)
