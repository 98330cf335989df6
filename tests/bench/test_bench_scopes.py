"""The reduction of a trace by the program's layer scopes (bench/scopes.py),
on hand-built events and HLO text, and the scopes in train steps compiled
on the CPU as the benchmark builds them."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common, scopes  # noqa: E402
from bench.trace import Event  # noqa: E402

common.use_repo_sources(ROOT)
NAMES = scopes.names()
MS = 1e6
S = "jit(train_step)"

HLO = f"""HloModule jit_train_step, entry_computation_layout={{(f32[4,8]{{1,0}})->f32[]}}

%fused_computation.2 (param_0.1: f32[2,4,8], param_1.2: s32[]) -> bf16[4,8] {{
  %param_0.1 = f32[2,4,8]{{2,1,0}} parameter(0)
  %dynamic-slice.1 = f32[1,4,8]{{2,1,0}} dynamic-slice(%param_0.1, %param_1.2), metadata={{op_name="{S}/jvp()/while/body/dynamic_slice"}}
  %convert.3 = bf16[1,4,8]{{2,1,0}} convert(%dynamic-slice.1), metadata={{op_name="{S}/jvp()/while/body/closed_call/attn.proj/convert_element_type"}}
  ROOT %bitcast.4 = bf16[4,8]{{1,0}} bitcast(%convert.3)
}}

%fused_computation.5 (param_0.6: f32[2,4,8]) -> f32[2,4,8] {{
  ROOT %dynamic-update-slice.7 = f32[2,4,8]{{2,1,0}} dynamic-update-slice(%param_0.6), metadata={{op_name="{S}/transpose(jvp())/while/body/dynamic_update_slice"}}
}}

ENTRY %main.9 (Arg_0.1: f32[4,8]) -> f32[] {{
  %Arg_0.1 = f32[4,8]{{1,0}} parameter(0), metadata={{op_name="state.params[\\'embed\\']"}}
  %fusion.1 = bf16[4,8]{{1,0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2
  %fusion.2 = f32[2,4,8]{{2,1,0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.5
  %dot.3 = f32[4,4]{{1,0}} dot(%fusion.1, %fusion.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}, metadata={{op_name="{S}/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn.proj/attn.core/dot_general" stack_frame_id=3}}
  %fusion.4 = f32[4]{{0}} fusion(%dot.3), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{S}/jvp()/while/body/closed_call/attn.proj/mul;{S}/optim/mul"}}
  %add.5 = f32[4]{{0}} add(%fusion.4, %fusion.4), metadata={{op_name="{S}/optim/add"}}
  ROOT %multiply.6 = f32[] multiply(%add.5), metadata={{op_name="{S}/transpose(jvp(loss))/loss/mul"}}
}}
"""


def dev(i, name, start_ms, dur_ms):
    return Event(f"/device:TPU:{i}", "XLA Ops", name, start_ms * MS,
                 dur_ms * MS)


def host(name, start_ms, dur_ms, line="python"):
    return Event("/host:CPU", line, name, start_ms * MS, dur_ms * MS)


def test_scope_names_come_from_the_program():
    from repro import scopes as program
    assert NAMES == program.NAMES
    assert len(set(NAMES)) == len(NAMES)
    for n in ("embed", "attn.proj", "attn.core", "mlp", "moe.router",
              "moe.dispatch", "moe.experts", "moe.combine", "head", "loss",
              "optim", "pipe.exchange", "grad_sync"):
        assert n in NAMES


@pytest.mark.parametrize("op_name, want", [
    (f"{S}/transpose(jvp(loss))/loss/mul", "loss"),
    (f"{S}/jvp(embed)/jit(_take)/gather", "embed"),
    (f"{S}/transpose(jvp(head))/dot_general", "head"),
    (f"{S}/transpose(jvp())/while/body/closed_call/checkpoint/"
     f"rematted_computation/attn.proj/attn.core/dot_general", "attn.core"),
    (f"{S}/jvp()/while/body/closed_call/mlp/jit(silu)/mul", "mlp"),
    (f"{S}/jvp()/while/body/closed_call/attn.proj/mul;{S}/optim/mul",
     "attn.proj"),
    (f"{S}/optim/mul;{S}/jvp()/while/body/closed_call/mlp/mul", "mlp"),
    (f"{S}/jvp()/while/body/dynamic_slice", "unscoped"),
    (f"{S}/mlpx/attn.projection/jit(loss)/mul", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name, NAMES) == want


def test_op_names_read_metadata_and_what_a_fusion_fuses():
    ops = scopes.op_names(HLO)
    assert ops["dot.3"].endswith("attn.core/dot_general")
    # no metadata of its own: the fused instructions' names, joined
    assert ops["fusion.1"] == (f"{S}/jvp()/while/body/dynamic_slice;{S}/"
                               f"jvp()/while/body/closed_call/attn.proj/"
                               f"convert_element_type")
    assert scopes.scope_of(ops["fusion.1"], NAMES) == "attn.proj"
    # the scan's gradient accumulation carries no scope
    assert scopes.scope_of(ops["fusion.2"], NAMES) == "unscoped"
    # a fusion's own metadata wins over what it fuses
    assert scopes.scope_of(ops["fusion.4"], NAMES) == "attn.proj"
    assert scopes.scope_of(ops["multiply.6"], NAMES) == "loss"
    assert ops["bitcast.4"] == ""
    assert scopes.instruction(
        "%dot.3 = f32[4,4]{1,0:T(8,128)} dot(%a, %b)") == "dot.3"
    assert scopes.instruction("fusion.1") == "fusion.1"


# A Mosaic call's ``kernel_metadata`` spans three lines of the compiled
# text, as the TPU compiler prints the splash kernel's calls; the rest of the
# layer's body follows it, and another computation after that
MOSAIC = f"""HloModule jit_train_step, entry_computation_layout={{(bf16[2,128,64]{{2,1,0}})->f32[]}}

%body.3 (q.1: bf16[2,128,64]) -> f32[2,128] {{
  %q.1 = bf16[2,128,64]{{2,1,0}} parameter(0)
  %splash_mha_fwd_residuals.1 = (f32[128,128]{{1,0}}, bf16[2,128,64]{{2,1,0}}) custom-call(%q.1, %q.1, %q.1), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\"block_q\": 1024, \"block_kv\": 1024, \"use_fused_bwd_kernel\": true}}"
}}}}, metadata={{op_name="{S}/jvp()/while/body/closed_call/attn.proj/jvp(attn.core)/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=13}}, backend_config={{"custom_call_config":{{"body":"TUzvUgFNTElS"}}}}
  %pallas_call.2 = bf16[2,128,64]{{2,1,0}} get-tuple-element(%splash_mha_fwd_residuals.1), index=1, frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\"block_q\": 1024}}"
}}}}, metadata={{op_name="{S}/jvp()/while/body/closed_call/attn.proj/jvp(attn.core)/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}}
  %multiply_reduce_fusion = f32[2,128]{{1,0}} fusion(%pallas_call.2), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{S}/transpose(jvp())/while/body/closed_call/attn.proj/transpose(jvp(attn.core))/dot_general"}}
  ROOT %convert.7 = f32[2,128]{{1,0}} convert(%multiply_reduce_fusion), metadata={{op_name="{S}/jvp()/while/body/closed_call/attn.proj/convert_element_type"}}
}}

%fused_computation.4 (param_0.5: bf16[2,128,64]) -> f32[2,128] {{
  %param_0.5 = bf16[2,128,64]{{2,1,0}} parameter(0)
  ROOT %reduce.6 = f32[2,128]{{1,0}} reduce(%param_0.5), dimensions={{2}}
}}

ENTRY %main.8 (q: bf16[2,128,64]) -> f32[] {{
  %q = bf16[2,128,64]{{2,1,0}} parameter(0)
  %call.9 = f32[2,128]{{1,0}} call(%q), to_apply=%body.3
  ROOT %reduce.10 = f32[] reduce(%call.9), dimensions={{0,1}}, metadata={{op_name="{S}/transpose(jvp(loss))/loss/reduce_sum"}}
}}
"""


def test_join_lines_keeps_a_multi_line_attribute_in_its_instruction():
    lines = scopes.join_lines(MOSAIC)
    assert not [ln for ln in lines if ln.startswith(('"', "}}"))]
    assert lines.count("}") == 3
    assert sum(1 for ln in lines if scopes.COMPUTATION.match(ln)) == 3
    assert scopes.join_lines(HLO) == HLO.splitlines()


def test_op_names_read_past_a_mosaic_call():
    ops = scopes.op_names(MOSAIC)
    # the call's own name lies on the last line of its metadata
    for name in ("splash_mha_fwd_residuals.1", "pallas_call.2"):
        assert ops[name].endswith("splash_mha_fwd_residuals/pallas_call")
        assert scopes.scope_of(ops[name], NAMES) == "attn.core"
    # the rest of the layer's body, after the call
    assert scopes.scope_of(ops["multiply_reduce_fusion"], NAMES) == "attn.core"
    assert scopes.scope_of(ops["convert.7"], NAMES) == "attn.proj"
    # and the computations after it
    assert scopes.scope_of(ops["call.9"], NAMES) == "unscoped"
    assert scopes.scope_of(ops["reduce.10"], NAMES) == "loss"
    assert set(ops) == {"q.1", "splash_mha_fwd_residuals.1", "pallas_call.2",
                        "multiply_reduce_fusion", "convert.7", "param_0.5",
                        "reduce.6", "q", "call.9", "reduce.10"}


def test_reduce_shares_past_a_mosaic_call():
    # window 0..100 ms: the splash call 0-40 and the fusion reading its
    # output 40-50 (attn.core), the convert 50-70 (attn.proj), the loss
    # 70-80, idle 80-100
    evs = [host("bench.window", 0, 100),
           dev(0, "%splash_mha_fwd_residuals.1 = (f32[128,128]) custom-call()",
               0, 40),
           dev(0, "multiply_reduce_fusion", 40, 10),
           dev(0, "convert.7", 50, 20), dev(0, "reduce.10", 70, 10)]
    red = scopes.reduce(evs, scopes.op_names(MOSAIC), NAMES)
    assert red["scopes"] == {"attn.core": pytest.approx(0.5),
                             "attn.proj": pytest.approx(0.2),
                             "loss": pytest.approx(0.1)}
    assert red["unscoped"] == pytest.approx(0.0)
    assert red["idle"] == pytest.approx(0.2)
    assert red["unmatched_s"] == 0.0


# The splash kernel's three calls in a layer of a rematerialised train step,
# with the op_names the TPU compiler gives them: the forward, the forward
# again inside the backward's rematerialised computation, and the fused
# backward; and the pipeline's plain forward, outside any jvp
SPLASH = "attn.proj/attn.core/cond/branch_0_fun/vmap(jit(_splash_attention))"
KERNELS = f"""HloModule jit_train_step, entry_computation_layout={{(bf16[8,15,64,2048]{{3,2,1,0}})->f32[]}}

ENTRY %main.1 (q: bf16[8,15,64,2048]) -> f32[] {{
  %q = bf16[8,15,64,2048]{{3,2,1,0}} parameter(0)
  %splash_mha_fwd_residuals.19 = (bf16[8,15,2048,64]{{3,2,1,0}}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/jvp()/while/body/closed_call/{SPLASH}/splash_mha_fwd_residuals/pallas_call"}}
  %splash_mha_fwd_residuals.18 = (bf16[8,15,2048,64]{{3,2,1,0}}) custom-call(%q), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\"block_q\": 1024}}"
}}}}, metadata={{op_name="{S}/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/{SPLASH}/splash_mha_fwd_residuals/pallas_call"}}
  %splash_mha_dkv_no_residuals.9 = (bf16[8,15,2048,64]{{3,2,1,0}}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/transpose(jvp())/while/body/closed_call/checkpoint/{SPLASH}/splash_mha_dkv_no_residuals/pallas_call"}}
  %splash_mha_fwd_no_residuals.6 = (bf16[8,15,2048,64]{{3,2,1,0}}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/shard_map/while/body/closed_call/checkpoint/{SPLASH}/splash_mha_fwd_no_residuals/pallas_call"}}
  %custom-call.3 = s32[16384]{{0}} custom-call(%q), custom_call_target="AssumeGatherIndicesInBound", metadata={{op_name="{S}/jvp(embed)/gather"}}
  %fusion.7 = f32[8,2048]{{1,0}} fusion(%q), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="{S}/transpose(jvp())/while/body/closed_call/checkpoint/{SPLASH}/mul"}}
  ROOT %reduce.10 = f32[] reduce(%fusion.7), dimensions={{0,1}}, metadata={{op_name="{S}/transpose(jvp(loss))/loss/reduce_sum"}}
}}
"""


def test_kernels_are_the_mosaic_calls():
    assert scopes.kernels(KERNELS) == {
        "splash_mha_fwd_residuals.19", "splash_mha_fwd_residuals.18",
        "splash_mha_dkv_no_residuals.9", "splash_mha_fwd_no_residuals.6"}
    assert scopes.kernels(MOSAIC) == {"splash_mha_fwd_residuals.1"}
    assert scopes.kernels(HLO) == set()


def test_kernel_runs_by_kernel_and_direction():
    # window 0..100 ms on two devices; each runs a layer's forward,
    # rematerialised forward and backward, and the first a pipeline's plain
    # forward; runs of no time, after the window, or of no kernel, and
    # the backward's fusion, are not counted
    evs = [host("bench.window", 0, 100)]
    for i in (0, 1):
        evs += [dev(i, "splash_mha_fwd_residuals.19", 0, 10),
                dev(i, "%splash_mha_fwd_residuals.18 = (bf16[8,15,2048,64]) "
                       "custom-call(%q)", 20, 10),
                dev(i, "splash_mha_dkv_no_residuals.9", 30, 15),
                dev(i, "fusion.7", 45, 5), dev(i, "custom-call.3", 50, 1),
                dev(i, "splash_mha_dkv_no_residuals.9", 60, 0),
                dev(i, "splash_mha_dkv_no_residuals.9", 120, 10)]
    evs.append(dev(0, "splash_mha_fwd_no_residuals.6", 70, 5))
    ops = scopes.op_names(KERNELS)
    got = scopes.kernel_runs(evs, ops, scopes.kernels(KERNELS), NAMES)
    assert got == {"attn.core": {
        "forward": {"splash_mha_fwd_residuals": 4,
                    "splash_mha_fwd_no_residuals": 1},
        "backward": {"splash_mha_dkv_no_residuals": 2}}}
    assert scopes.kernel_runs(evs, ops, set(), NAMES) == {}
    assert scopes.kernel_runs([host("bench.window", 0, 1)], ops,
                              scopes.kernels(KERNELS), NAMES) == {}


def test_strip_metadata():
    stripped = scopes.strip_metadata(HLO)
    assert "metadata" not in stripped and "op_name" not in stripped
    assert "calls=%fused_computation.2" in stripped


@pytest.fixture
def two_devices():
    # window 0..100 ms.  Device 0: attention scores 0-10, projections
    # 10-40, the scan's gradient accumulation 40-50, optimizer 50-60 and
    # 95-110 (clipped at 100; one more before the window), idle 60-95.
    # Device 1: mlp 0-50, an op the HLO does not hold 50-60.
    return [
        host("bench.window", 0, 100), host("bench.fetch", 60, 35),
        host("np.asarray(jax.Array)", 60, 35),
        host("TransferFromDevice", 62, 30, line="pjrt"),
        host("thread", -10, 200, line="pjrt"),
        dev(0, "%dot.3 = f32[4,4]{1,0} dot(a, b)", 0, 10),
        dev(0, "fusion.1", 10, 30),
        dev(0, "fusion.2", 40, 10),
        dev(0, "add.5", 50, 10), dev(0, "add.5", 95, 15),
        dev(0, "add.5", -20, 10),
        dev(1, "mlp.7", 0, 50), dev(1, "custom.9", 50, 10),
    ]


def hlo_with_mlp():
    return dict(scopes.op_names(HLO),
                **{"mlp.7": f"{S}/jvp()/while/body/closed_call/mlp/dot"})


def test_reduce_shares_per_device_and_mean(two_devices):
    red = scopes.reduce(two_devices, hlo_with_mlp(), NAMES)
    d0, d1 = red["per_device"][0], red["per_device"][1]
    assert d0["scopes"] == {"attn.core": pytest.approx(0.1),
                            "attn.proj": pytest.approx(0.3),
                            "optim": pytest.approx(0.15)}
    assert d0["unscoped"] == pytest.approx(0.1)
    assert d0["idle"] == pytest.approx(0.35)
    assert d1["scopes"] == {"mlp": pytest.approx(0.5)}
    assert d1["unscoped"] == pytest.approx(0.1)
    assert d1["idle"] == pytest.approx(0.4)
    assert red["scopes"]["mlp"] == pytest.approx(0.25)
    assert red["scopes"]["attn.proj"] == pytest.approx(0.15)
    assert red["unscoped"] == pytest.approx(0.1)
    assert red["idle"] == pytest.approx(0.375)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["unmatched_s"] == pytest.approx(0.01 / 2)
    for d in (d0, d1):
        assert (sum(d["scopes"].values()) + d["unscoped"] + d["idle"]
                == pytest.approx(1.0))


def test_unscoped_ops_are_named(two_devices):
    red = scopes.reduce(two_devices, hlo_with_mlp(), NAMES)
    ops = dict(red["unscoped_ops"])
    assert ops == {
        f"fusion.2 {S}/transpose(jvp())/while/body/dynamic_update_slice":
            pytest.approx(0.005),
        "custom.9": pytest.approx(0.005)}


def test_loop_time_between_body_ops_is_unscoped():
    # a while loop 0-60 encloses body ops 0-20 (mlp) and 30-60 (optim): the
    # loop's own 10 ms between them is busy, and unscoped
    hlo = {"while.1": f"{S}/jvp()/while",
           "a": f"{S}/jvp()/while/body/closed_call/mlp/dot",
           "b": f"{S}/optim/add"}
    evs = [host("bench.window", 0, 100), dev(0, "while.1", 0, 60),
           dev(0, "a", 0, 20), dev(0, "b", 30, 30)]
    d = scopes.reduce(evs, hlo, NAMES)["per_device"][0]
    assert d["scopes"] == {"mlp": pytest.approx(0.2),
                           "optim": pytest.approx(0.3)}
    assert d["unscoped"] == pytest.approx(0.1)
    assert d["idle"] == pytest.approx(0.4)


def test_an_op_followed_by_a_zero_length_op_stays_innermost():
    # the chip's trace holds zero-length ops (custom calls, async ends)
    # inside or at the end of another op; a loop still encloses its body
    hlo = {"f": f"{S}/jvp()/while/body/closed_call/attn.proj/attn.core/exp",
           "g": f"{S}/jvp()/while/body/closed_call/attn.proj/attn.core/dot",
           "z": "", "while.1": f"{S}/jvp()/while",
           "a": f"{S}/jvp()/while/body/closed_call/mlp/dot"}
    evs = [host("bench.window", 0, 100), dev(0, "f", 0, 20),
           dev(0, "z", 20, 0), dev(0, "g", 20, 20), dev(0, "z", 30, 0),
           dev(0, "while.1", 50, 30), dev(0, "a", 50, 30)]
    assert [e.name for e in scopes.leaves(evs)] == ["f", "g", "a"]
    d = scopes.reduce(evs, hlo, NAMES)["per_device"][0]
    assert d["scopes"] == {"attn.core": pytest.approx(0.4),
                           "mlp": pytest.approx(0.3)}
    assert d["unscoped"] == pytest.approx(0.0)
    assert d["idle"] == pytest.approx(0.3)


def test_a_program_without_scopes_reads_nothing(two_devices):
    plain = {k: v.replace("attn.", "x.").replace("optim", "o")
             .replace("loss", "l") for k, v in scopes.op_names(HLO).items()}
    assert scopes.reduce(two_devices, plain, NAMES) is None
    assert scopes.reduce(two_devices, scopes.op_names(HLO), ()) is None


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        scopes.reduce([host("bench.window", 0, 10)], scopes.op_names(HLO),
                      NAMES)


def test_gap_causes_name_the_host_event(two_devices):
    gaps = scopes.gap_causes(two_devices)
    # device 0 is idle 60-95: the transfer covers 30 ms of it; the Python
    # thread's own event and the harness span are no runtime events, and
    # the thread event spans the whole window and explains nothing
    assert gaps[0] == [pytest.approx(0.035), "TransferFromDevice",
                       pytest.approx(0.030), "bench.fetch"]
    assert len(gaps) == 1


def test_gap_causes_prefer_the_innermost_of_equal_cover():
    evs = [host("bench.window", 0, 100), dev(0, "a", 0, 40),
           dev(0, "a", 60, 40), host("Execute", 30, 40, line="pjrt"),
           host("Await", 38, 24, line="pjrt")]
    assert scopes.gap_causes(evs)[0][1:3] == ["Await", pytest.approx(0.02)]


# --- scopes in compiled train steps (CPU) ---------------------------------

TINY = {"arch": "smollm_360m", "reference": "decoder", "hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "vocab_pad_multiple": 256}
TINY_MOE = dict(TINY, arch="granite_moe_1b_a400m", intermediate_size=32,
                num_local_experts=4, num_experts_per_tok=2,
                router_aux_loss_coef=0.01)
MATMUL = r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ (?:dot|convolution)\("


def bench_parts():
    b = common.Bench(ROOT)
    return b.driver("train"), b.reference("decoder"), b.traffic


def compiled_hlo(mc, mix) -> str:
    import jax
    import jax.numpy as jnp
    driver, ref, _ = bench_parts()
    prog = driver.build(dict(mc, name="tiny"), mix, ref)
    state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    return prog.step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("mc, must", [
    (TINY, {"attn.proj", "attn.core", "mlp", "head"}),
    (TINY_MOE, {"attn.proj", "attn.core", "moe.router", "moe.experts",
                "head"}),
], ids=["dense", "moe"])
def test_every_matmul_of_the_step_is_scoped(mc, must):
    _, _, traffic = bench_parts()
    mix = dict(traffic("train.b8x2048"), batch=2, seq=32)
    hlo = compiled_hlo(mc, mix)
    ops = scopes.op_names(hlo)
    found = {}
    for line in hlo.splitlines():
        m = re.match(MATMUL, line)
        if m:
            found[m.group(1)] = scopes.scope_of(ops[m.group(1)], NAMES)
    assert found, "no matmul in the compiled step"
    assert "unscoped" not in found.values(), {
        k: ops[k] for k, v in found.items() if v == "unscoped"}
    assert must <= set(found.values())
    every = {scopes.scope_of(o, NAMES) for o in ops.values()}
    assert {"embed", "loss", "optim"} <= every
    if mc is TINY_MOE:
        assert {"moe.dispatch", "moe.combine"} <= every


@pytest.mark.parametrize("config", ["smollm-360m", "granite-moe-1b-a400m"])
def test_scopes_leave_the_compiled_step_the_same(config):
    """The step at the configuration's width (2 layers, a short sequence)
    compiles to the same program with each scope a no-op, apart from
    metadata."""
    import jax
    b = common.Bench(ROOT)
    driver, ref, traffic = bench_parts()
    mc = dict(b.config(config), num_hidden_layers=2)
    mix = dict(traffic("train.b8x2048"), batch=1, seq=128)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        got = scopes.same_program(driver.build, mc, mix, ref)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    assert got["same"]
    assert got["instructions"][0] == got["instructions"][1] > 100


PIPE_CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from bench import common
b = common.Bench({root!r})
mix = dict(b.traffic("train.pipe2-dp2.b32x2048"), batch=8, seq=32,
           parallel="pipe=2,micro=2,sched=1f1b,dp=2")
prog = b.driver("train").build(json.loads({mc!r}), mix, b.reference("decoder"))
state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
batch = {{k: jax.ShapeDtypeStruct((8, 32), jnp.int32) for k in ("tokens", "labels")}}
with jax.set_mesh(prog.mesh):
    print(prog.step.lower(state, batch).compile().as_text())
"""


def test_pipeline_exchange_and_grad_sync_on_four_devices():
    code = PIPE_CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                             mc=json.dumps(dict(TINY, name="tiny")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    hlo = p.stdout
    ops = scopes.op_names(hlo)
    kinds = {}
    for line in hlo.splitlines():
        m = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? "
                     r"(collective-permute|all-reduce)(?:-start)?\(", line)
        if m:
            kinds.setdefault(m.group(2), []).append(m.group(1))
    permutes = {scopes.scope_of(ops[n], NAMES)
                for n in kinds["collective-permute"]}
    assert permutes == {"pipe.exchange"}
    reduces = [n for n in kinds["all-reduce"]
               if scopes.scope_of(ops[n], NAMES) == "grad_sync"]
    assert reduces
    # the reduction finds both on every device of a trace of those ops
    evs = [host("bench.window", 0, 100)]
    for d in range(4):
        evs += [dev(d, kinds["collective-permute"][0], 0, 10),
                dev(d, reduces[0], 10, 5)]
    red = scopes.reduce(evs, ops, NAMES)
    assert red["scopes"] == {"pipe.exchange": pytest.approx(0.1),
                             "grad_sync": pytest.approx(0.05)}
    assert red["idle"] == pytest.approx(0.85)
