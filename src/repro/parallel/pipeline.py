"""Pipeline model-parallelism subsystem: schedules + shard_map runtime.

The paper implements MP for GNMT/BigLSTM as pipeline parallelism (§4.4); on
TPU the idiomatic equivalent streams micro-batches through mesh-axis stages
with ``jax.lax.ppermute``.  ``ParallelPlan(mp_kind="pipeline")`` selects this
runtime.  This module is schedule-generic: a ``PipelineSchedule`` object
emits the per-tick (stage, micro-batch, chunk, direction) table plus its
analytic bubble / peak-activation model, and ``pipeline_apply`` executes the
schedule's forward placement over the mesh axis.

Schedules (S stages, K micro-batches, v virtual chunks per device)
==================================================================

``gpipe``       all forwards, then all backwards (the seed behaviour)::

                    t:  0  1  2  3  4  5 | 6  7  8  9 10 11      (S=2, K=4)
                 s=0:  F0 F1 F2 F3  .  . |  .  .  B3 B2 B1 B0
                 s=1:   . F0 F1 F2 F3  . |  B3 B2 B1 B0  .  .

                bubble = (S-1)/(K+S-1); activation residency = K
                micro-batches per stage (every forward's boundary stays live
                until its backward).

``1f1b``        same warmup, then each stage alternates one-backward /
                one-forward, draining activations as soon as the downstream
                backward returns::

                    t:  0  1  2  3  4  5  6  7  8  9 10 11       (S=2, K=4)
                 s=0:  F0 F1  . B0 F2 B1 F3 B2  . B3  .  .
                 s=1:   . F0 B0 F1 B1 F2 B2 F3 B3  .  .  .

                bubble = (S-1)/(K+S-1) — identical per-step time to GPipe —
                but activation residency is min(K, S-s) at stage s, i.e. at
                most S micro-batches live instead of K.  That is the whole
                point: at equal memory budget 1F1B admits K' >= K_gpipe
                micro-batches, and the *larger feasible K* is what shrinks
                the effective bubble.

``interleaved`` v non-contiguous layer chunks per device (virtual stages
                j = 0..vS-1, chunk j//S living on device j%S); micro-batches
                traverse the stage ring v times via a wrap-around
                ``ppermute``.  Forward wave (S=2, K=2, v=2; Xc = micro X,
                chunk c)::

                    t:  0   1   2   3   4
                 s=0:  0a  1a  0b  1b   .
                 s=1:   .  0a  1a  0b  1b

                bubble = (S-1)/(vK+S-1) when S | K (the packed Megatron
                wave) — v times smaller than GPipe/1F1B — at the cost of v
                times the inter-stage ``ppermute`` traffic and a slightly
                higher residency min(K, S + (S-1)/v).

Memory model (per device, micro-batch units of per-device layer activations):

    ============  =======================  ==============================
    schedule      bubble fraction          activation residency
    ============  =======================  ==============================
    gpipe         (S-1)/(K+S-1)            K
    1f1b          (S-1)/(K+S-1)            min(K, S)
    interleaved   (S-1)/(vK+S-1)  [S | K]  min(K, S + (S-1)/v)
    ============  =======================  ==============================

Two runtimes execute a schedule:

``pipeline_apply`` (the **ad** runtime) executes the schedule's *forward*
tick table (placement, injection, chunk selection, wrap ring) inside one
``shard_map``-ed ``lax.scan``; the backward pass is generated by jax AD
through that scan.  On this substrate the fwd/bwd *interleaving* of 1F1B is
not independently executable — gpipe and 1f1b share one forward dataflow,
are bit-identical at runtime, and every schedule *executes* with
GPipe-like memory (all K micro-batch boundaries live across the
fwd->bwd transpose).

``pipeline_value_and_grad`` (the **scheduled** runtime) executes the
**complete** fwd+bwd ``PipelineSchedule.table()`` as a hand-scheduled loop:
each tick a stage either runs a forward ``WorkUnit`` (``stage_fn``,
stashing its stage-input boundary in an explicit activation store sized by
the schedule's residency bound), or the mirrored backward ``WorkUnit``
(``jax.vjp`` re-linearizes the stage at the stashed input, pops the slot,
accumulates per-stage parameter grads) — cotangents ride a second
``ppermute`` ring in the reverse direction.  The backward wave of the
1F1B example above (``S=2, K=4``; lower-case = backward)::

        t:  0  1  2  3  4  5  6  7  8  9
     s=0:  F0 F1  . b0 F2 b1 F3 b2  . b3      store peak = min(K, S) = 2
     s=1:   . F0 b0 F1 b1 F2 b2 F3 b3  .

    vs gpipe, whose mirrored backward wave only starts after every
    forward has run — all K = 4 boundaries stay stashed::

        t:  0  1  2  3  4  5 | 6  7  8  9 10 11
     s=0:  F0 F1 F2 F3  .  . |  .  .  b3 b2 b1 b0   store peak = K = 4
     s=1:   . F0 F1 F2 F3  . |  b3 b2 b1 b0  .  .

This is what realizes 1F1B's min(K, S) activation residency *at runtime*
(``plan_scheduled_runtime`` sizes the store from the table; the ad runtime
realizes K regardless of schedule), and it is what the planner's
schedule-aware memory filter assumes when ``ParallelPlan.runtime ==
"scheduled"``.  The ad path stays alive (``--pipe-runtime ad``) for
bit-for-bit differential testing.

Data parallelism composes orthogonally: ``batch_axes`` shards the per-micro
batch dim over the DP mesh axes inside the same shard_map, so a dp x stages
mesh runs N independent pipelines with the gradient all-reduce over the DP
axes inserted by GSPMD outside the stage axis.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import scopes
from repro.parallel.jaxcompat import shard_map

SCHEDULE_KINDS = ("gpipe", "1f1b", "interleaved")


@dataclasses.dataclass(frozen=True)
class WorkUnit:
    """One scheduled cell of the full fwd+bwd table."""
    tick: int
    stage: int       # physical device on the stage axis
    micro: int
    chunk: int       # virtual-chunk index on that device (0 for v=1)
    direction: str   # "fwd" | "bwd"


@dataclasses.dataclass(frozen=True)
class PipelineSchedule:
    """A pipeline schedule: placement tables + analytic cost/memory model.

    ``n_virtual_per_stage`` (v) is the interleaved chunk count per device;
    gpipe/1f1b require v == 1.
    """

    kind: str
    n_stages: int
    n_micro: int
    n_virtual_per_stage: int = 1

    def __post_init__(self):
        if self.kind not in SCHEDULE_KINDS:
            raise ValueError(f"unknown schedule {self.kind!r}; "
                             f"expected one of {SCHEDULE_KINDS}")
        if self.kind != "interleaved" and self.n_virtual_per_stage != 1:
            raise ValueError(f"{self.kind} takes no virtual stages")
        if self.kind == "interleaved" and self.n_virtual_per_stage < 2:
            raise ValueError("interleaved needs >= 2 chunks per device")
        if self.n_stages < 1 or self.n_micro < 1:
            raise ValueError((self.n_stages, self.n_micro))

    # -- geometry ----------------------------------------------------------

    @property
    def v(self) -> int:
        return self.n_virtual_per_stage

    @property
    def n_virtual(self) -> int:
        """Total virtual stages; chunk j//S of virtual stage j on device j%S."""
        return self.n_stages * self.v

    def _wave(self) -> Tuple[int, int]:
        """(round size R, round stride) of the forward wave.

        v == 1 admits the full single wave (micro m enters stage 0 at tick
        m).  v > 1 wraps activations around the stage ring, so at most S
        micro-batches may be in flight per chunk round; when S | K the
        rounds pack back-to-back (stride S*v — the Megatron interleaved
        wave), otherwise they are spaced so the wrap edge stays
        conflict-free (stride R + vS - 1).
        """
        S, K, v = self.n_stages, self.n_micro, self.v
        if v == 1:
            return K, 0
        R = min(S, K)
        stride = S * v if K % S == 0 else R + self.n_virtual - 1
        return R, stride

    def _fwd_tick(self, m: int, j: int) -> int:
        """Tick at which micro ``m`` runs virtual stage ``j`` forward."""
        R, stride = self._wave()
        return (m // R) * stride + (m % R) + j

    @property
    def fwd_ticks(self) -> int:
        return self._fwd_tick(self.n_micro - 1, self.n_virtual - 1) + 1

    # -- tables ------------------------------------------------------------

    def forward_table(self) -> Dict[str, np.ndarray]:
        """The executable forward placement, as (T, S) int32 arrays.

        ``micro[t, s]`` — micro-batch processed by stage s at tick t (-1 if
        idle); ``chunk[t, s]`` — which of the device's v layer chunks;
        ``inject[t, s]`` — 1 when the input comes from the local micro-batch
        buffer (virtual stage 0) instead of the ppermute ring; ``emit[t, s]``
        — 1 when the output is a finished micro-batch (virtual stage vS-1).
        """
        S, K, V = self.n_stages, self.n_micro, self.n_virtual
        T = self.fwd_ticks
        micro = np.full((T, S), -1, np.int32)
        chunk = np.zeros((T, S), np.int32)
        inject = np.zeros((T, S), np.int32)
        emit = np.zeros((T, S), np.int32)
        for m in range(K):
            for j in range(V):
                t, s = self._fwd_tick(m, j), j % S
                assert micro[t, s] == -1, ("schedule conflict", self, t, s)
                micro[t, s] = m
                chunk[t, s] = j // S
                inject[t, s] = int(j == 0)
                emit[t, s] = int(j == V - 1)
        return {"micro": micro, "chunk": chunk, "inject": inject, "emit": emit}

    def table(self) -> List[WorkUnit]:
        """Full fwd+bwd schedule, one WorkUnit per busy (tick, stage) cell.

        gpipe: forward wave + time-mirrored backward wave.  1f1b: event
        simulation of the one-forward-one-backward policy with the per-stage
        in-flight cap min(K, S-s).  interleaved: the packed forward wave +
        the 1f1b-style simulated backward with the Megatron per-device
        in-flight chunk cap min(Kv, (v-1)S + 2(S-1-d) + 1).  Backward ticks
        are costed equal to forward ticks (the classic idealization behind
        the closed-form bubbles; real bwd ~ 2x fwd changes the constants,
        not the fractions).
        """
        if self.kind == "gpipe":
            return self._mirrored_table()
        return self._simulated_table()

    def _mirrored_table(self) -> List[WorkUnit]:
        S, V, Tf = self.n_stages, self.n_virtual, self.fwd_ticks
        units = []
        for m in range(self.n_micro):
            for j in range(V):
                tf = self._fwd_tick(m, j)
                units.append(WorkUnit(tf, j % S, m, j // S, "fwd"))
                units.append(WorkUnit(2 * Tf - 1 - tf, j % S, m, j // S,
                                      "bwd"))
        return sorted(units, key=lambda u: (u.tick, u.stage))

    def _inflight_cap(self, device: int) -> int:
        """Max fwd-done-not-yet-bwd chunks device ``device`` may hold."""
        S, K, v = self.n_stages, self.n_micro, self.v
        if self.v == 1:
            return min(K, S - device)
        return min(K * v, (v - 1) * S + 2 * (S - 1 - device) + 1)

    def _simulated_table(self) -> List[WorkUnit]:
        """Greedy event simulation of the 1F1B policy over virtual stages.

        Ready rule: fwd(m, j) needs fwd(m, j-1) done at an earlier tick;
        bwd(m, j) needs bwd(m, j+1) (or, for j = vS-1, fwd(m, vS-1)) done at
        an earlier tick.  Each device runs one unit per tick, preferring
        backward (that is the 1F1B drain), and refusing forwards that would
        exceed its in-flight cap.  A safety valve lifts the cap if the whole
        machine ever stalls, guaranteeing termination.
        """
        S, K, V = self.n_stages, self.n_micro, self.n_virtual
        fwd_done = {}   # (m, j) -> completion tick
        bwd_done = {}
        inflight = [0] * S
        units: List[WorkUnit] = []
        t = 0
        limit = 4 * (2 * K * V + 2 * self.fwd_ticks) + 16
        while len(bwd_done) < K * V:
            for relax_cap in (False, True):
                scheduled_any = False
                for s in range(S):
                    unit = self._pick(s, t, fwd_done, bwd_done, inflight,
                                      relax_cap)
                    if unit is None:
                        continue
                    m, j, d = unit
                    units.append(WorkUnit(t, s, m, j // S, d))
                    if d == "fwd":
                        fwd_done[(m, j)] = t
                        inflight[s] += 1
                    else:
                        bwd_done[(m, j)] = t
                        inflight[s] -= 1
                    scheduled_any = True
                if scheduled_any:
                    break
                # nothing schedulable: dependencies only ever complete when a
                # unit is scheduled, so a fully idle strict tick is a cap
                # deadlock — retry this tick with the cap lifted
            t += 1
            assert t < limit, ("schedule simulation diverged", self)
        return sorted(units, key=lambda u: (u.tick, u.stage))

    def _pick(self, s: int, t: int, fwd_done, bwd_done, inflight,
              relax_cap: bool) -> Optional[Tuple[int, int, str]]:
        S, K, V = self.n_stages, self.n_micro, self.n_virtual
        my_vstages = range(s, V, S)
        # 1F1B: drain a backward first whenever one is ready
        best = None
        for j in my_vstages:
            for m in range(K):
                if (m, j) in bwd_done or (m, j) not in fwd_done:
                    continue
                if j == V - 1:
                    ready = fwd_done[(m, j)] < t
                else:
                    ready = (m, j + 1) in bwd_done and bwd_done[(m, j + 1)] < t
                if ready and (best is None or (m, j) < best):
                    best = (m, j)
        if best is not None:
            return (*best, "bwd")
        if inflight[s] >= self._inflight_cap(s) and not relax_cap:
            return None
        for j in my_vstages:
            for m in range(K):
                if (m, j) in fwd_done:
                    continue
                ready = (j == 0 or ((m, j - 1) in fwd_done
                                    and fwd_done[(m, j - 1)] < t))
                if ready and (best is None or (m, j) < best):
                    best = (m, j)
        return None if best is None else (*best, "fwd")

    # -- analytics ---------------------------------------------------------

    def total_ticks(self) -> int:
        tbl = self.table()
        return tbl[-1].tick + 1 if tbl else 0

    def bubble_fraction(self) -> float:
        """Idle fraction of the steady schedule (closed form; the planner's
        SU^M input).  gpipe/1f1b: (S-1)/(K+S-1).  interleaved: derived from
        the forward wave — (S-1)/(vK+S-1) when S | K, the relaxed-wave
        equivalent otherwise."""
        S, K, v = self.n_stages, self.n_micro, self.v
        if S <= 1:
            return 0.0
        if self.v == 1:
            return (S - 1) / (K + S - 1)
        return 1.0 - (K * v) / self.fwd_ticks

    def activation_residency(self) -> float:
        """Peak live micro-batches of per-device layer activations (closed
        form; the planner's memory-filter input).  Derivable from table():
        see test_pipeline_schedule."""
        S, K, v = self.n_stages, self.n_micro, self.v
        if self.kind == "gpipe":
            return float(K)
        if self.kind == "1f1b":
            return float(min(K, S))
        return min(float(K), S + (S - 1) / v)

    def residency_from_table(self) -> float:
        """Peak fwd-done-not-yet-bwd chunks per device, from table(), in
        micro-batch units (chunks / v) — the cross-check for
        activation_residency()."""
        peak = 0
        live = {}
        for u in self.table():
            key = u.stage
            live.setdefault(key, 0)
            live[key] += 1 if u.direction == "fwd" else -1
            peak = max(peak, live[key])
        return peak / self.v

    def describe(self) -> str:
        v = f" v={self.v}" if self.v > 1 else ""
        return (f"{self.kind}[{self.n_stages} stages x {self.n_micro} micro"
                f"{v}] bubble={self.bubble_fraction():.3f} "
                f"resid={self.activation_residency():.1f}")


def make_schedule(kind: str, n_stages: int, n_micro: int,
                  virtual_stages: int = 1) -> PipelineSchedule:
    """Normalizing constructor: v is forced to 1 for gpipe/1f1b and defaults
    to 2 for interleaved when unspecified."""
    if kind != "interleaved":
        virtual_stages = 1
    elif virtual_stages <= 1:
        virtual_stages = 2
    return PipelineSchedule(kind=kind, n_stages=n_stages, n_micro=n_micro,
                            n_virtual_per_stage=virtual_stages)


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def _prepare_pipeline(mesh, axis: str, stage_params, x, n_micro: int,
                      batch_axes, schedule, virtual_stages: int):
    """Shared operand normalization for both pipeline runtimes.

    Resolves the schedule, validates the ``stack_to_stages`` params layout,
    splits ``x`` into micro-batches sharded over the DP axes.  Returns
    ``(sched, baxes, bspec, x_spec, xm)``.
    """
    from jax.sharding import NamedSharding

    n_stages = mesh.shape[axis]
    sched = (schedule if isinstance(schedule, PipelineSchedule)
             else make_schedule(schedule, n_stages, n_micro, virtual_stages))
    assert sched.n_stages == n_stages and sched.n_micro == n_micro, \
        (sched, n_stages, n_micro)
    v = sched.v
    # the params layout must agree with the schedule's chunk count —
    # make_schedule normalizes v (gpipe/1f1b force 1, interleaved defaults
    # 2), and a silent mismatch would apply the wrong layer chunks
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[:2] != (n_stages, v):
            raise ValueError(
                f"stage_params leading dims {leaf.shape[:2]} do not match "
                f"(n_stages={n_stages}, v={v}) for schedule "
                f"{sched.kind!r}; stack with stack_to_stages(params, "
                f"{n_stages}, {v})")
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    if isinstance(batch_axes, str):        # a single mesh-axis name
        batch_axes = (batch_axes,)
    baxes = tuple(a for a in (batch_axes or ()) if a)
    if baxes:
        dp = 1
        for a in baxes:
            dp *= mesh.shape[a]
        assert mb % dp == 0, (mb, baxes, dp)
    bspec = baxes if baxes else None
    x_spec = P(None, bspec, *([None] * (x.ndim - 1)))
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    xm = jax.lax.with_sharding_constraint(xm, NamedSharding(mesh, x_spec))
    return sched, baxes, bspec, x_spec, xm


def pipeline_apply(mesh, axis: str, stage_fn: Callable, stage_params, x,
                   n_micro: int, batch_axes=(), schedule="gpipe",
                   virtual_stages: int = 1, replicate_out: bool = False):
    """Run ``x`` through a layer stack partitioned into stages over ``axis``.

    stage_params: pytree with leading dims (n_stages, v, layers_per_chunk,
    ...) — the ``stack_to_stages`` layout.
    stage_fn(params_one_chunk, x) -> y applies one chunk's layers.
    x: (B, ...) with B divisible by n_micro (and B // n_micro divisible by
    the ``batch_axes`` mesh size, which shards each micro-batch for dp x
    stages execution).
    schedule: "gpipe" | "1f1b" | "interleaved" or a PipelineSchedule.
    replicate_out: legacy output handling — psum the full outs buffer over
    the stage axis (every stage contributes zeros except the last), i.e. a
    2*(S-1)/S * |outs| all-reduce every step.  The default instead streams
    each finished micro-batch around a dedicated single-source **broadcast
    ring**: the emitting stage inserts its fresh output into a second
    per-tick ``ppermute`` channel and every stage copies the arriving value
    into its own outs buffer, so replication costs one micro-batch-sized
    ppermute per tick (plus S-1 extra drain ticks) and no all-reduce at
    all; see test_pipeline_output_broadcast_bytes.  AD stays exact: the
    shard_map transpose splits the replicated output's cotangent across the
    stage copies, and the ring's transposed ppermutes route every copy's
    share back to the emitting stage where they sum to the full cotangent
    (test_pipeline_dp_stages_grads_equal_pure_dp pins this bit-for-bit).

    Returns y with x's shape.  Bit-exact (fp32) vs sequential stacking for
    every schedule.
    """
    n_stages = mesh.shape[axis]
    sched, baxes, bspec, x_spec, xm = _prepare_pipeline(
        mesh, axis, stage_params, x, n_micro, batch_axes, schedule,
        virtual_stages)
    v = sched.v

    tbl = sched.forward_table()
    micro_np, chunk_np = tbl["micro"], tbl["chunk"]
    inject_np, emit_np = tbl["inject"], tbl["emit"]
    drain = 0 if replicate_out else n_stages - 1
    if drain:
        # extend the tick tables by S-1 idle rows so the last emitted
        # micro-batch can circulate the broadcast ring to every stage
        pad = lambda a, fill: np.concatenate(
            [a, np.full((drain, n_stages), fill, a.dtype)])
        micro_np, chunk_np = pad(micro_np, -1), pad(chunk_np, 0)
        inject_np, emit_np = pad(inject_np, 0), pad(emit_np, 0)
    # bcast_np[t, s] — micro-batch index whose finished output arrives at
    # stage s over the broadcast ring at tick t (emitted by stage S-1 at
    # tick t - (s+1), the hop distance S-1 -> 0 -> ... -> s), else -1
    T_ext = micro_np.shape[0]
    bcast_np = np.full((T_ext, n_stages), -1, np.int32)
    for s in range(n_stages - 1):
        for t in range(T_ext):
            te = t - (s + 1)
            if te >= 0 and emit_np[te, n_stages - 1]:
                bcast_np[t, s] = micro_np[te, n_stages - 1]
    t_micro, t_chunk = jnp.asarray(micro_np), jnp.asarray(chunk_np)
    t_inject, t_emit = jnp.asarray(inject_np), jnp.asarray(emit_np)
    t_bcast = jnp.asarray(bcast_np)
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def inner(params_local, xm_local):
        # params_local: (1, v, layers_per_chunk, ...) — this stage's slice
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        state0 = jnp.zeros_like(xm_local[0])
        outs0 = jnp.zeros_like(xm_local)

        def tick(carry, rows):
            state, bcast, outs = carry
            mrow, crow, irow, erow, brow = rows
            m = jnp.take(mrow, stage)
            m_idx = jnp.clip(m, 0, n_micro - 1)
            inp = jnp.where(jnp.take(irow, stage) == 1, xm_local[m_idx], state)
            if v == 1:
                chunk_params = jax.tree.map(lambda a: a[0], params_local)
            else:
                c = jnp.clip(jnp.take(crow, stage), 0, v - 1)
                chunk_params = jax.tree.map(
                    lambda a: jnp.take(a, c, axis=0), params_local)
            y = stage_fn(chunk_params, inp)
            write = jnp.take(erow, stage) == 1
            outs = outs.at[m_idx].set(jnp.where(write, y, outs[m_idx]))
            with scopes.scope(scopes.PIPE_EXCHANGE):
                state = jax.lax.ppermute(y, axis, ring)
            if not replicate_out:
                # write the arriving broadcast value (its share of the
                # output cotangent rides the transposed ring back to the
                # emitting stage), then forward — or insert this tick's
                # fresh emission
                bm = jnp.take(brow, stage)
                bv = bcast
                outs = outs.at[jnp.clip(bm, 0, n_micro - 1)].set(
                    jnp.where(bm >= 0,
                              bv, outs[jnp.clip(bm, 0, n_micro - 1)]))
                with scopes.scope(scopes.PIPE_EXCHANGE):
                    bcast = jax.lax.ppermute(
                        jnp.where(write, y, bcast), axis, ring)
            return (state, bcast, outs), None

        (_, _, outs), _ = jax.lax.scan(
            tick, (state0, state0, outs0),
            (t_micro, t_chunk, t_inject, t_emit, t_bcast))
        if replicate_out:
            # legacy: replicate by summing the one non-zero contribution
            return jax.lax.psum(
                jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
                axis)
        # the broadcast ring already replicated outs onto every stage
        return outs

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    out = shard_map(inner, mesh=mesh, in_specs=(p_specs, x_spec),
                    out_specs=x_spec)(stage_params, xm)
    return out.reshape(x.shape)


def stack_to_stages(stacked_params, n_stages: int, virtual_stages: int = 1):
    """(L, ...) stacked layer params -> (n_stages, v, L/(n_stages*v), ...).

    Chunk c on device s holds virtual stage j = c*S + s, i.e. layers
    [j*Lc, (j+1)*Lc) — contiguous blocks, so sharding the leading dim over
    the stage axis is exactly per-stage parameter residency.
    """
    v = max(virtual_stages, 1)

    def re(a):
        L = a.shape[0]
        if L % (n_stages * v):
            raise ValueError(
                f"stack_to_stages: leading (layer) axis of size {L} is not "
                f"divisible by n_stages * virtual_stages = {n_stages} * {v} "
                f"= {n_stages * v}; pick a stage/chunk count that evenly "
                f"partitions the stack (models.api.pipeline_applicable)")
        lc = L // (n_stages * v)
        a = a.reshape((v, n_stages, lc) + a.shape[1:])
        return jnp.swapaxes(a, 0, 1)

    return jax.tree.map(re, stacked_params)


def stages_to_stack(stage_tree, n_stages: int, virtual_stages: int = 1):
    """Inverse of ``stack_to_stages``: (n_stages, v, L/(n_stages*v), ...) ->
    (L, ...).  Used to reassemble the per-stage gradients the scheduled
    runtime emits back into the model's stacked-layer params layout."""
    v = max(virtual_stages, 1)

    def re(a):
        if a.shape[:2] != (n_stages, v):
            raise ValueError(
                f"stages_to_stack: leading dims {a.shape[:2]} do not match "
                f"(n_stages={n_stages}, v={v})")
        a = jnp.swapaxes(a, 0, 1)            # (v, S, Lc, ...)
        return a.reshape((n_stages * v * a.shape[2],) + a.shape[3:])

    return jax.tree.map(re, stage_tree)


# ---------------------------------------------------------------------------
# hand-scheduled fwd+bwd runtime
# ---------------------------------------------------------------------------

_ZERO_TABLES = ("op", "micro", "chunk", "f_inject", "f_emit", "b_first")
_SLOT_TABLES = ("f_slot", "b_seed", "f_arr", "b_arr", "b_rd", "b_act")


@dataclasses.dataclass(frozen=True)
class ScheduledRuntimePlan:
    """Static executable form of the full fwd+bwd ``WorkUnit`` table.

    ``tables`` are (n_ticks, n_stages) int32 arrays the runtime scans over:

    - ``op``        0 idle | 1 forward unit | 2 backward unit
    - ``micro`` / ``chunk``  the unit's micro-batch / device-chunk index
    - ``f_inject`` / ``f_emit``  forward unit is virtual stage 0 / vS-1
    - ``f_slot``    activation-store slot holding the forward unit's stage
                    input (written from ``xm`` at exec for injected units,
                    pre-written by the ring arrival otherwise)
    - ``f_arr``     slot an arriving forward activation lands in (-1 none)
    - ``b_seed``    cotangent-store slot the emit tick's loss seed lands in
    - ``b_arr``     slot an arriving ring cotangent lands in (-1 none)
    - ``b_rd``      backward unit's incoming-cotangent slot (freed)
    - ``b_act``     backward unit's stashed stage-input slot (freed)
    - ``b_first``   backward unit is virtual stage 0 (dx -> input cotangent)

    ``fwd_slots`` is the activation store size — the live-buffer high-water
    mark across stages and ticks, in stage-input (micro-batch x chunk)
    buffers.  For the v=1 schedules it equals the closed-form
    ``activation_residency()`` exactly: min(K, S) for 1f1b vs K for gpipe
    (test_pipeline_runtime pins this over the schedule grid).  Interleaved
    stores chunk inputs (1/v micro-batch units each) and may buffer up to
    v-1 *in-transit* chunks above residency * v — an input that arrived
    over a wrap ring before its forward tick while the stage still holds
    its steady-state chunks; the planner's ring-buffer memory term covers
    these.
    """
    schedule: PipelineSchedule
    n_ticks: int
    fwd_slots: int
    bwd_slots: int
    stage_high_water: Tuple[int, ...]
    tables: Dict[str, Any] = dataclasses.field(repr=False, compare=False,
                                               default_factory=dict)

    @property
    def high_water(self) -> int:
        """Peak live stashed stage-input buffers across stages (== the
        allocated store size; the satellite residency metric)."""
        return max(self.stage_high_water)


class _SlotAllocator:
    """Per-stage linear-scan slot allocator (smallest free index first)."""

    def __init__(self):
        self.free: List[int] = []
        self.n = 0
        self.live = 0
        self.peak = 0

    def get(self) -> int:
        self.live += 1
        self.peak = max(self.peak, self.live)
        if self.free:
            return heapq.heappop(self.free)
        self.n += 1
        return self.n - 1

    def put(self, slot: int) -> None:
        self.live -= 1
        heapq.heappush(self.free, slot)


def plan_scheduled_runtime(sched: PipelineSchedule) -> ScheduledRuntimePlan:
    """Compile ``sched.table()`` into the per-tick tables + store sizes.

    Slot lifetimes follow the dataflow, not the unit placement: a stage
    input is allocated when it *arrives* over the ring (production tick + 1
    of the upstream forward unit) or, for injected units, at the forward
    tick itself, and freed when the mirrored backward unit consumes it.
    Within a tick every allocation lands before any free (the runtime lands
    arrivals before executing the tick's unit), so a slot freed by this
    tick's backward is not reusable by this tick's arrival.
    """
    S, K, V = sched.n_stages, sched.n_micro, sched.n_virtual
    units = sched.table()
    T = units[-1].tick + 1
    fwd_exec: Dict[Tuple[int, int], Tuple[int, int]] = {}
    bwd_exec: Dict[Tuple[int, int], Tuple[int, int]] = {}
    for u in units:
        j = u.chunk * S + u.stage
        ex = fwd_exec if u.direction == "fwd" else bwd_exec
        ex[(u.micro, j)] = (u.tick, u.stage)
    assert len(fwd_exec) == len(bwd_exec) == K * V, sched

    tbl = {n: np.zeros((T, S), np.int32) for n in _ZERO_TABLES}
    tbl.update({n: np.full((T, S), -1, np.int32) for n in _SLOT_TABLES})

    # allocation / free events, bucketed by tick
    allocs: Dict[int, list] = {}
    frees: Dict[int, list] = {}
    for (m, j), (te, s) in sorted(fwd_exec.items()):
        if j == 0:
            allocs.setdefault(te, []).append((s, 0, "f_stash", m, j, te))
        else:
            tp, _ = fwd_exec[(m, j - 1)]
            assert tp < te, (sched, m, j)
            allocs.setdefault(tp + 1, []).append((s, 0, "f_arr", m, j, tp + 1))
        if j == V - 1:
            allocs.setdefault(te, []).append((s, 1, "b_seed", m, j, te))
    for (m, j), (tb, s) in sorted(bwd_exec.items()):
        if j < V - 1:
            tp, _ = bwd_exec[(m, j + 1)]
            assert tp < tb, (sched, m, j)
            allocs.setdefault(tp + 1, []).append((s, 1, "b_arr", m, j, tp + 1))
        frees.setdefault(tb, []).append((s, m, j))

    fsl = [_SlotAllocator() for _ in range(S)]
    bsl = [_SlotAllocator() for _ in range(S)]
    f_slot_of: Dict[Tuple[int, int], int] = {}
    b_slot_of: Dict[Tuple[int, int], int] = {}
    for t in range(T):
        for s, _, kind, m, j, ta in sorted(allocs.get(t, [])):
            if kind in ("f_stash", "f_arr"):
                slot = fsl[s].get()
                f_slot_of[(m, j)] = slot
                if kind == "f_arr":
                    assert tbl["f_arr"][ta, s] == -1, (sched, ta, s)
                    tbl["f_arr"][ta, s] = slot
            else:
                slot = bsl[s].get()
                b_slot_of[(m, j)] = slot
                if kind == "b_arr":
                    assert tbl["b_arr"][ta, s] == -1, (sched, ta, s)
                    tbl["b_arr"][ta, s] = slot
        for s, m, j in sorted(frees.get(t, [])):
            fsl[s].put(f_slot_of[(m, j)])
            bsl[s].put(b_slot_of[(m, j)])
    assert all(a.live == 0 for a in fsl + bsl), sched

    for (m, j), (te, s) in fwd_exec.items():
        tbl["op"][te, s] = 1
        tbl["micro"][te, s] = m
        tbl["chunk"][te, s] = j // S
        tbl["f_inject"][te, s] = int(j == 0)
        tbl["f_emit"][te, s] = int(j == V - 1)
        tbl["f_slot"][te, s] = f_slot_of[(m, j)]
        if j == V - 1:
            tbl["b_seed"][te, s] = b_slot_of[(m, j)]
    for (m, j), (tb, s) in bwd_exec.items():
        tbl["op"][tb, s] = 2
        tbl["micro"][tb, s] = m
        tbl["chunk"][tb, s] = j // S
        tbl["b_first"][tb, s] = int(j == 0)
        tbl["b_rd"][tb, s] = b_slot_of[(m, j)]
        tbl["b_act"][tb, s] = f_slot_of[(m, j)]

    return ScheduledRuntimePlan(
        schedule=sched, n_ticks=T,
        fwd_slots=max(a.n for a in fsl),
        bwd_slots=max(a.n for a in bsl),
        stage_high_water=tuple(a.peak for a in fsl),
        tables=tbl)


def pipeline_value_and_grad(mesh, axis: str, stage_fn: Callable,
                            stage_params, x, *, loss_fn: Callable,
                            loss_params, targets=None, n_micro: int,
                            batch_axes=(), schedule="gpipe",
                            virtual_stages: int = 1):
    """Hand-scheduled fwd+bwd pipeline training step (the **scheduled**
    runtime): executes the complete ``PipelineSchedule.table()`` —
    forward *and* backward ``WorkUnit``s, in the schedule's interleaving —
    instead of letting jax AD synthesize the backward through the forward
    scan.

    - ``stage_fn(chunk_params, x) -> y`` applies one device-chunk of layers
      (pure, shape-preserving — so ``jax.vjp`` per unit is well defined).
    - ``loss_fn(loss_params, y_micro, target_micro) -> scalar`` maps one
      *finished* micro-batch to its additive loss contribution (the total
      loss is the sum over micro-batches; fold any global normalizer — e.g.
      1/valid-token-count — into ``loss_params``).  It runs on the emitting
      stage at the emit tick, seeding that micro-batch's backward wave.
    - ``targets``: optional pytree of per-example targets with x's leading
      batch dim; split into micro-batches alongside ``x``.

    Per tick each stage runs one ``lax.switch`` branch: **forward** (stash
    the stage input into the activation store at its statically-allocated
    slot, apply ``stage_fn``, and on emit ticks ``jax.vjp`` the loss to
    accumulate its value + loss-param grads and seed the output cotangent),
    **backward** (pop the incoming cotangent and the stashed stage input,
    re-linearize ``stage_fn`` there, accumulate this chunk's parameter
    grads, and hand the input cotangent to the upstream stage), or
    **idle**.  Forward activations ride the forward ``ppermute`` ring;
    cotangents ride the mirror ring in the reverse direction.  The store
    holds exactly ``plan_scheduled_runtime(sched).fwd_slots`` stage-input
    buffers — min(K, S) for 1f1b vs K for gpipe — which is the whole point:
    the ad runtime keeps all K alive regardless of schedule.

    Returns ``(loss, (stage_grads, loss_param_grads, dx))`` where
    ``stage_grads`` matches the ``stack_to_stages`` layout of
    ``stage_params``, ``loss_param_grads`` matches ``loss_params``, and
    ``dx`` is the cotangent of ``x`` (backprop it through the embedding
    outside).  Matches ``jax.value_and_grad`` of the ad runtime to fp32
    bit-noise (summation order differs) — pinned on the schedule x stages x
    micro grid by test_pipeline_runtime.
    """
    from jax.sharding import NamedSharding

    n_stages = mesh.shape[axis]
    sched, baxes, bspec, x_spec, xm = _prepare_pipeline(
        mesh, axis, stage_params, x, n_micro, batch_axes, schedule,
        virtual_stages)
    v = sched.v
    rtp = plan_scheduled_runtime(sched)
    t_rows = {k: jnp.asarray(a) for k, a in rtp.tables.items()}

    tgt = {} if targets is None else targets
    tgt_m = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(
            a.reshape((n_micro, a.shape[0] // n_micro) + a.shape[1:]),
            NamedSharding(mesh, P(None, bspec, *([None] * (a.ndim - 1))))),
        tgt)
    loss_params = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(
            jnp.asarray(a), NamedSharding(mesh, P())), loss_params)

    ring_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    ring_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def inner(params_local, xm_local, lp, tgt_local):
        # params_local: (1, v, layers_per_chunk, ...) — this stage's slice
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        act0 = jnp.zeros_like(xm_local[0])
        contrib_sds = jax.eval_shape(
            loss_fn, lp, jax.ShapeDtypeStruct(act0.shape, act0.dtype),
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                tgt_local))

        def store_put(store, slot, val):
            i = jnp.clip(slot, 0, store.shape[0] - 1)
            return store.at[i].set(jnp.where(slot >= 0, val, store[i]))

        def tick(carry, rows):
            fmsg, bmsg, fstore, bstore, gacc, dxm, loss_acc, lpg = carry
            r = {k: jnp.take(rows[k], stage) for k in rows}
            # 1. land last tick's ring messages before executing anything:
            # a slot freed by this tick's backward is never reused by this
            # tick's arrival (plan_scheduled_runtime guarantees it)
            fstore = store_put(fstore, r["f_arr"], fmsg)
            bstore = store_put(bstore, r["b_arr"], bmsg)
            m_idx = jnp.clip(r["micro"], 0, n_micro - 1)
            c_idx = jnp.clip(r["chunk"], 0, v - 1)
            if v == 1:
                chunk_params = jax.tree.map(lambda a: a[0], params_local)
            else:
                chunk_params = jax.tree.map(
                    lambda a: jnp.take(a, c_idx, axis=0), params_local)

            # 2. this tick's WorkUnit — one switch branch per direction, so
            # a tick costs one stage_fn *or* one vjp, never both
            def run_idle(ops):
                fstore, bstore, gacc, dxm, loss_acc, lpg = ops
                return (fstore, bstore, gacc, dxm, loss_acc, lpg,
                        act0, act0)

            def run_fwd(ops):
                fstore, bstore, gacc, dxm, loss_acc, lpg = ops
                fs = jnp.clip(r["f_slot"], 0, rtp.fwd_slots - 1)
                fstore = fstore.at[fs].set(
                    jnp.where(r["f_inject"] == 1, xm_local[m_idx],
                              fstore[fs]))
                y = stage_fn(chunk_params, fstore[fs])

                def run_seed(sops):
                    bstore, loss_acc, lpg = sops
                    t_m = jax.tree.map(lambda a: a[m_idx], tgt_local)
                    contrib, vjp_fn = jax.vjp(loss_fn, lp, y, t_m)
                    dlp, dy, _ = vjp_fn(jnp.ones_like(contrib))
                    return (store_put(bstore, r["b_seed"], dy),
                            loss_acc + contrib,
                            jax.tree.map(jnp.add, lpg, dlp))

                bstore, loss_acc, lpg = jax.lax.cond(
                    r["f_emit"] == 1, run_seed, lambda sops: sops,
                    (bstore, loss_acc, lpg))
                return (fstore, bstore, gacc, dxm, loss_acc, lpg, y, act0)

            def run_bwd(ops):
                fstore, bstore, gacc, dxm, loss_acc, lpg = ops
                g = bstore[jnp.clip(r["b_rd"], 0, rtp.bwd_slots - 1)]
                inp = fstore[jnp.clip(r["b_act"], 0, rtp.fwd_slots - 1)]
                _, vjp_fn = jax.vjp(stage_fn, chunk_params, inp)
                dgp, dxv = vjp_fn(g)
                gacc = jax.tree.map(
                    lambda ga, d: ga.at[c_idx].add(d), gacc, dgp)
                first = r["b_first"] == 1
                dxm = dxm.at[m_idx].add(jnp.where(first, dxv, act0))
                return (fstore, bstore, gacc, dxm, loss_acc, lpg, act0,
                        jnp.where(first, act0, dxv))

            ops = (fstore, bstore, gacc, dxm, loss_acc, lpg)
            (fstore, bstore, gacc, dxm, loss_acc, lpg, fy, bg) = \
                jax.lax.switch(jnp.clip(r["op"], 0, 2),
                               (run_idle, run_fwd, run_bwd), ops)
            # 3. ring sends — collectives stay outside the switch so every
            # stage participates every tick
            with scopes.scope(scopes.PIPE_EXCHANGE):
                fmsg = jax.lax.ppermute(fy, axis, ring_fwd)
                bmsg = jax.lax.ppermute(bg, axis, ring_bwd)
            return (fmsg, bmsg, fstore, bstore, gacc, dxm, loss_acc,
                    lpg), None

        carry0 = (
            act0, act0,
            jnp.zeros((rtp.fwd_slots,) + act0.shape, act0.dtype),
            jnp.zeros((rtp.bwd_slots,) + act0.shape, act0.dtype),
            jax.tree.map(jnp.zeros_like, params_local),
            jnp.zeros_like(xm_local),
            jnp.zeros(contrib_sds.shape, contrib_sds.dtype),
            jax.tree.map(jnp.zeros_like, lp))
        (_, _, _, _, gacc, dxm, loss_acc, lpg), _ = jax.lax.scan(
            tick, carry0, t_rows)

        # loss / loss-param grads live on the emitting stage (others hold
        # zeros); stage grads are per-stage partials over the DP shards;
        # dxm is stage 0's input cotangent, per DP shard
        reduce_axes = (axis,) + baxes
        with scopes.scope(scopes.GRAD_SYNC):
            loss = jax.lax.psum(loss_acc, reduce_axes)
            lpg = jax.tree.map(lambda a: jax.lax.psum(a, reduce_axes), lpg)
            if baxes:
                gacc = jax.tree.map(lambda a: jax.lax.psum(a, baxes), gacc)
        with scopes.scope(scopes.PIPE_EXCHANGE):
            dxm = jax.lax.psum(dxm, axis)
        return loss, jax.tree.map(lambda a: a[None], gacc), lpg, dxm

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    lp_specs = jax.tree.map(lambda _: P(), loss_params)
    tgt_specs = jax.tree.map(
        lambda a: P(None, bspec, *([None] * (a.ndim - 2))), tgt_m)
    loss, stage_grads, lp_grads, dxm = shard_map(
        inner, mesh=mesh,
        in_specs=(p_specs, x_spec, lp_specs, tgt_specs),
        out_specs=(P(), p_specs, lp_specs, x_spec))(
            stage_params, xm, loss_params, tgt_m)
    return loss, (stage_grads, lp_grads, dxm.reshape(x.shape))


# ---------------------------------------------------------------------------
# analytic model (planner inputs)
# ---------------------------------------------------------------------------

def pipeline_bubble_fraction(n_micro: int, n_stages: int,
                             schedule: str = "gpipe",
                             virtual_stages: int = 1) -> float:
    """Idle fraction of the schedule — the analytic SU^M input for
    pipeline-MP in the planner (per-step speedup = m * (1 - bubble))."""
    if n_stages <= 1:
        return 0.0
    return make_schedule(schedule, n_stages, n_micro,
                         virtual_stages).bubble_fraction()


def pipeline_activation_residency(n_micro: int, n_stages: int,
                                  schedule: str = "gpipe",
                                  virtual_stages: int = 1,
                                  runtime: str = "scheduled") -> float:
    """Peak live micro-batches of per-device activations — the planner's
    schedule-aware memory-filter input, keyed off the runtime that will
    actually execute the plan: the hand-scheduled runtime realizes the
    schedule's residency bound, while the ad runtime keeps all K
    micro-batch boundaries live across the fwd->bwd transpose regardless
    of schedule (so 1f1b buys nothing there)."""
    if runtime == "ad":
        return float(max(n_micro, 1))
    return make_schedule(schedule, n_stages, n_micro,
                         virtual_stages).activation_residency()


def pipeline_step_speedup(m: int, n_micro: int, comm_fraction: float = 0.0,
                          schedule: str = "gpipe",
                          virtual_stages: int = 1) -> float:
    """SU^M of m-stage pipelining with n_micro micro-batches: perfect split
    minus bubble minus inter-stage activation-transfer overhead (the caller
    scales ``comm_fraction`` by v for interleaved's extra ring traffic)."""
    if m <= 1:
        return 1.0
    eff = 1.0 - pipeline_bubble_fraction(n_micro, m, schedule, virtual_stages)
    return m * eff / (1.0 + comm_fraction)
