"""Multi-replica router: health-checked failover, shedding, elasticity.

The load-bearing pins (the PR's acceptance criteria):
- a request whose replica is KILLED mid-decode completes on another
  replica with tokens AND logprobs bit-identical to the same request on
  an unfaulted single-replica run (shared engine seed + (rid, n_gen)-
  addressed sampling keys + replay-based re-prefill — see
  ``serve/router.py``'s failover state machine);
- the same bit-equality when the replica STALLS past the watchdog or
  emits NaN logprobs (``nanlogits``; the poisoned suffix is discarded and
  regenerated, never delivered);
- exact accounting: every submitted rid appears in ``results`` exactly
  once — completed, shed (projected wait / bounded queue), or timed out;
- deadline-aware retry: a failover whose backoff cannot beat the deadline
  times out instead of wasting a dispatch;
- elastic drain/grow mirrors PR 7's elastic DP: a draining replica
  finishes its work, is removed, and a grown replica serves bit-identical
  continuations.

Prompts within a test share one length: a new prompt length retraces the
jitted prefill (seconds of XLA compile), which the armed watchdog would
flag as a stall.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import ContinuousEngine, ReplicaRouter, Request
from repro.train.fault import Fault, parse_fault_schedule

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def _setup(seed=0):
    cfg = get_config("llama3_2_1b").reduced()
    api = build_model(cfg, remat=False)
    params = api.init(jax.random.PRNGKey(seed))
    return cfg, api, params


def _reqs(n=4, max_new=6, **kw):
    return [Request(rid=i, tokens=[1 + i, 2 + i, 3 + i, 4 + i],
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _solo_ref(api, params, n=4, max_new=6):
    """Unfaulted single-replica reference (same seed as every router
    replica): the bitwise target for all failover paths."""
    eng = ContinuousEngine(api, params, n_slots=2, capacity=32)
    return {r.rid: r for r in eng.run(_reqs(n, max_new))}


def _assert_bit_equal(results, ref):
    assert sorted(r.rid for r in results) == sorted(ref)
    for r in results:
        assert r.tokens == ref[r.rid].tokens, r.rid
        assert r.logprobs == ref[r.rid].logprobs, r.rid
        assert r.finished_reason in ("eos", "length")


def test_no_fault_router_matches_solo_and_accounts_every_rid():
    cfg, api, params = _setup()
    ref = _solo_ref(api, params)
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32)
    out = rt.run(_reqs())
    _assert_bit_equal(out, ref)
    assert rt.stats == {"completed": 4, "shed": 0, "timed_out": 0,
                        "failovers": 0}
    assert rt.replica_states == ["healthy", "healthy"]


def test_kill_midflight_failover_bit_identical():
    """THE acceptance pin: replica 0 dies with requests mid-decode; they
    complete on replica 1 bit-identical to the unfaulted run."""
    cfg, api, params = _setup()
    ref = _solo_ref(api, params)
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32,
                       faults=parse_fault_schedule("kill@3:0"),
                       retry_backoff_s=0.0)
    for r in _reqs():
        rt.submit(r)
    rt.step()
    rt.step()
    # genuinely mid-decode: replica 0's requests have generated tokens
    pre = {rid: list(tr.tokens) for rid, tr in rt.tracked.items()
           if tr.replica == 0}
    assert pre and all(len(t) > 0 for t in pre.values())
    rt.step()                              # tick 3: the kill fires
    assert rt.replica_states[0] == "dead"
    assert rt.fault_log == [("kill", 3, 0)]
    assert rt.stats["failovers"] == len(pre)
    while rt.step():
        pass
    _assert_bit_equal(sorted(rt.results, key=lambda r: r.rid), ref)
    assert rt.stats["completed"] == 4 and rt.stats["timed_out"] == 0


def test_stall_past_watchdog_failover_bit_identical():
    """A replica hanging past the watchdog is degraded (heartbeat reuse of
    ``train.fault.Watchdog``) and its requests fail over bit-identically;
    the stalled tick's own output is still valid (detection-only)."""
    cfg, api, params = _setup()
    ref = _solo_ref(api, params)
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32,
                       faults=parse_fault_schedule("stall@3:0:0.5"),
                       watchdog_timeout_s=0.15, retry_backoff_s=0.0)
    out = rt.run(_reqs())
    rt.close()
    assert rt.replica_states == ["degraded", "healthy"]
    assert ("stall", 3, 0) in rt.fault_log
    assert rt.stats["failovers"] > 0
    _assert_bit_equal(out, ref)


def test_nanlogits_degrades_replica_and_regenerates_poisoned_suffix():
    """NaN-logit health check: the poisoned replica is quarantined, the
    non-finite suffix is never delivered, and the re-generated
    continuation is bit-identical to the unfaulted run."""
    cfg, api, params = _setup()
    ref = _solo_ref(api, params)
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32,
                       faults=parse_fault_schedule("nanlogits@2:1"),
                       retry_backoff_s=0.0)
    out = rt.run(_reqs())
    assert rt.replica_states == ["healthy", "degraded"]
    assert all(np.isfinite(lp) for r in out for lp in r.logprobs)
    _assert_bit_equal(out, ref)


def test_projected_wait_and_bounded_queue_shed_exactly_once():
    """Load shedding both ways — projected wait > deadline at the door,
    and per-engine ``max_queue`` overflow — with every rid accounted."""
    cfg, api, params = _setup()
    # projected-wait: the EWMA step estimate prices the backlog out
    rt = ReplicaRouter(api, params, replicas=1, n_slots=1, capacity=32,
                       est_step_s=10.0)
    assert rt.submit(Request(rid=0, tokens=[1, 2, 3],
                             max_new_tokens=4)) is None
    shed = rt.submit(Request(rid=1, tokens=[1, 2, 3], max_new_tokens=4,
                             deadline_s=1.0))
    assert shed is not None and shed.finished_reason == "shed"
    while rt.step():
        pass
    assert sorted(r.rid for r in rt.results) == [0, 1]
    assert rt.stats["shed"] == 1 and rt.stats["completed"] == 1

    # bounded queue: the engine's max_queue rejection surfaces as a
    # router shed with router-side accounting (no double count)
    rt2 = ReplicaRouter(api, params, replicas=1, n_slots=1, capacity=32,
                        max_queue=1)
    rt2.submit(Request(rid=0, tokens=[1, 2], max_new_tokens=2))
    rt2.submit(Request(rid=1, tokens=[1, 2], max_new_tokens=2))
    shed2 = rt2.submit(Request(rid=2, tokens=[1, 2], max_new_tokens=2))
    assert shed2 is not None and shed2.finished_reason == "shed"
    while rt2.step():
        pass
    assert sorted(r.rid for r in rt2.results) == [0, 1, 2]
    assert sum(r.finished_reason == "shed" for r in rt2.results) == 2


def test_deadline_aware_retry_times_out_instead_of_wasted_dispatch():
    """A failover whose capped backoff cannot beat the request deadline is
    finalized "timed_out" immediately — no pointless re-dispatch."""
    cfg, api, params = _setup()
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32,
                       faults=parse_fault_schedule("kill@2:0"),
                       retry_backoff_s=100.0, max_retry_backoff_s=100.0,
                       clock=lambda: 0.0)
    for r in _reqs(n=4, max_new=6, deadline_s=5.0):
        rt.submit(r)
    while rt.step():
        pass
    res = {r.rid: r for r in rt.results}
    assert sorted(res) == [0, 1, 2, 3]
    reasons = {r.finished_reason for r in res.values()}
    assert "timed_out" in reasons            # replica 0's requests
    assert rt.stats["timed_out"] == rt.stats["failovers"] > 0


def test_drain_and_grow_bit_identical():
    """Elastic shrink/grow: a draining replica finishes its in-flight work
    and is removed; a grown replica (same seed) serves new dispatches with
    unchanged results."""
    cfg, api, params = _setup()
    ref = _solo_ref(api, params, n=6)
    rt = ReplicaRouter(api, params, replicas=2, n_slots=2, capacity=32)
    reqs = _reqs(n=6)
    for r in reqs[:4]:
        rt.submit(r)
    rt.step()
    rt.drain_replica(0)
    assert rt.add_replica() == 2
    for r in reqs[4:]:                     # lands on the grown replica
        rt.submit(r)
    assert any(tr.replica == 2 for tr in rt.tracked.values())
    while rt.step():
        pass
    assert rt.replica_states == ["removed", "healthy", "healthy"]
    _assert_bit_equal(sorted(rt.results, key=lambda r: r.rid), ref)


def test_router_rejects_training_form_faults_and_duplicate_rids():
    cfg, api, params = _setup()
    with pytest.raises(ValueError, match="replica-keyed"):
        ReplicaRouter(api, params, replicas=1, n_slots=1, capacity=32,
                      faults=[Fault("kill", 3)])      # no replica
    with pytest.raises(ValueError, match="replica-keyed"):
        ReplicaRouter(api, params, replicas=1, n_slots=1, capacity=32,
                      faults=parse_fault_schedule("fail@3"))
    rt = ReplicaRouter(api, params, replicas=1, n_slots=2, capacity=32)
    rt.submit(Request(rid=7, tokens=[1, 2], max_new_tokens=2))
    with pytest.raises(ValueError, match="already in flight"):
        rt.submit(Request(rid=7, tokens=[3, 4], max_new_tokens=2))


def test_from_choice_executes_replicas_axis():
    """``InferenceChoice.build_router`` executes the planner's ``replicas``
    axis: one engine group per planned replica, each tp=1 replica's params
    on its own device, serving bit-identically to one engine."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        from repro.configs import get_config
        from repro.core.planner import InferenceChoice
        from repro.models import build_model
        from repro.parallel.plan import serve_plan
        from repro.serve import ContinuousEngine, Request

        api = build_model(get_config("llama3_2_1b").reduced(), remat=False)
        params = api.init(jax.random.PRNGKey(0))
        reqs = lambda: [Request(rid=i, tokens=[1+i, 2+i, 3+i, 4+i],
                                max_new_tokens=6) for i in range(4)]
        eng = ContinuousEngine(api, params, n_slots=2, capacity=32)
        ref = {r.rid: r for r in eng.run(reqs())}
        choice = InferenceChoice(replicas=2, tp=1, slots=2, step_latency=1e-3,
                                 tokens_per_s=1.0, mem_bytes=0.0,
                                 mesh_shape=(2, 1), plan=serve_plan(1))
        rt = choice.build_router(api, params, capacity=32)
        assert len(rt.replicas) == choice.replicas
        assert all(r.engine.n_slots == choice.slots for r in rt.replicas)
        devs = [jax.tree.leaves(r.engine.params)[0].devices()
                for r in rt.replicas]
        assert devs[0] != devs[1] and all(len(d) == 1 for d in devs), devs
        out = rt.run(reqs())
        assert sorted(r.rid for r in out) == sorted(ref)
        for r in out:
            assert r.tokens == ref[r.rid].tokens, r.rid
            assert r.logprobs == ref[r.rid].logprobs, r.rid
            assert r.finished_reason in ("eos", "length")
        print("FROM_CHOICE_OK")
    """)
    assert "FROM_CHOICE_OK" in out


def test_serve_cli_replicas_on_distinct_devices():
    """``launch.serve --continuous --replicas 4 --tp 1`` on four forced host
    devices puts each replica's params on a different device, and its
    greedy tokens equal the one-engine run of the same prompts."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.launch.serve import main

        args = ["--arch", "smollm_360m", "--reduced", "--continuous",
                "--slots", "2", "--batch", "6", "--prompt-len", "8",
                "--max-new", "4", "--prefill-chunk", "4"]
        one = main(args)
        four = main(args + ["--replicas", "4"])
        devs = [set(jax.tree.leaves(e.params)[0].devices())
                for e in four["engines"]]
        assert len(devs) == 4 and all(len(d) == 1 for d in devs), devs
        assert len(set.union(*devs)) == 4, devs
        assert [r.tokens for r in four["results"]] == \\
            [r.tokens for r in one["results"]]
        assert four["stats"]["completed"] == 6
        print("CLI_REPLICAS_OK")
    """)
    assert "CLI_REPLICAS_OK" in out


@pytest.mark.slow
def test_from_choice_tp_replica_groups_kill_failover_subprocess():
    """replicas=2 x tp=2 on four forced host devices: each replica group
    gets a DISJOINT 2-device mesh, and a kill mid-decode still completes
    bit-identical to an unfaulted single TP group (same decode geometry,
    so even the logprob bits match)."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import get_config
        from repro.core.planner import InferenceChoice
        from repro.models import build_model
        from repro.parallel.plan import serve_plan
        from repro.serve import ContinuousEngine, Request
        from repro.train.fault import parse_fault_schedule

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        params = api.init(jax.random.PRNGKey(0))
        reqs = lambda: [Request(rid=i, tokens=[1+i, 2+i, 3+i, 4+i],
                                max_new_tokens=5) for i in range(4)]

        choice = InferenceChoice(replicas=2, tp=2, slots=2,
                                 step_latency=1e-3, tokens_per_s=1.0,
                                 mem_bytes=0.0, mesh_shape=(2, 2),
                                 plan=serve_plan(2))
        rt = choice.build_router(api, params, capacity=32,
                                 faults=parse_fault_schedule("kill@3:0"),
                                 retry_backoff_s=0.0)
        meshes = rt._meshes
        assert len(meshes) == 2
        d0 = {d.id for d in meshes[0].devices.flat}
        d1 = {d.id for d in meshes[1].devices.flat}
        assert d0 and d1 and not (d0 & d1), (d0, d1)   # disjoint groups

        out = rt.run(reqs())
        assert rt.replica_states[0] == "dead"

        # unfaulted single TP group with the same geometry and seed
        ref_eng = ContinuousEngine(api, params, n_slots=2, capacity=32,
                                   mesh=meshes[1], model_axis="model",
                                   batch_axes=("data",))
        ref = {r.rid: r for r in ref_eng.run(reqs())}
        for r in out:
            assert r.tokens == ref[r.rid].tokens, r.rid
            assert r.logprobs == ref[r.rid].logprobs, r.rid
        print("ROUTER_TP_OK", rt.stats)
    """)
    assert "ROUTER_TP_OK" in out
