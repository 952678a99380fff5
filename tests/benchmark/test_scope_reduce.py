"""The join from trace op to program scope, and what a named kernel call must
do: on a recorded fixture (cell gpt2s_pretrain_1k's entry computation and six
steps of its ops, from a traced run on the v5e, PR 24) and on hand-made
cases."""
import gzip
import json
import os
import re

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness, kernel_costs, scope_reduce as sr, \
    trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "gpt2s_step_scopes.json.gz")
REGISTERED = {"sdpa", "layer_norm", "linear", "matmul", "embedding",
              "cross_entropy_hard", "transpose", "reshape", "split", "add",
              "log_softmax"}
TRAINING_KERNELS = {"flash_fwd_causal", "flash_bwd_dq_causal",
                    "flash_bwd_dkv_causal", "layer_norm_fwd", "layer_norm_bwd",
                    "softmax_ce_fwd", "softmax_ce_bwd"}
PEAKS = harness.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        data = json.load(f)
    data["text"] = "\n".join(data["entry"])
    data["ops"] = [tuple(e) for e in data["ops"]]
    data["lines"], roots = sr.instructions(data["text"])
    data["table"] = sr.rows(data["lines"], roots, data["ops"], data["steps"],
                            REGISTERED)
    return data


# ---- the recorded step -------------------------------------------------------
def test_phases_and_the_unscoped_rest_add_up_to_busy_time(recorded):
    table = recorded["table"]
    busy_ms = tr.total(tr.union(tr.spans_of(recorded["ops"]))) / \
        recorded["steps"] / 1e6
    by_phase = sr.by(table, lambda r: r.phase)
    assert set(by_phase) <= set(sr.PHASES) | {None}
    assert sum(by_phase.values()) == pytest.approx(busy_ms, rel=1e-9)
    scoped = sum(ms for phase, ms in by_phase.items() if phase)
    assert scoped == pytest.approx(busy_ms - by_phase.get(None, 0.0),
                                   rel=1e-9)
    # the step is the one PERF.md section 5 describes
    assert 200 < busy_ms < 240
    assert by_phase["backward"] > by_phase["forward"] > by_phase["optimizer"] > 0
    assert by_phase.get(None, 0.0) < 0.001 * busy_ms
    moved = sum(r.ms for r in table if r.by_data_flow)
    assert 0.01 * busy_ms < moved < 0.03 * busy_ms


def test_kernel_names_are_exactly_the_seven_training_kernels(recorded):
    by_kernel = sr.by([r for r in recorded["table"] if r.kernel],
                      lambda r: r.kernel)
    assert set(by_kernel) == TRAINING_KERNELS
    calls = {k: 0 for k in TRAINING_KERNELS}
    for r in recorded["table"]:
        if r.kernel:
            calls[r.kernel] += r.calls
    assert calls == {"flash_fwd_causal": 12, "flash_bwd_dq_causal": 12,
                     "flash_bwd_dkv_causal": 12, "layer_norm_fwd": 25,
                     "layer_norm_bwd": 25, "softmax_ce_fwd": 1,
                     "softmax_ce_bwd": 1}


def test_program_ops_hold_their_kernels_forward_and_backward(recorded):
    for op, prefix in (("sdpa", "flash_"), ("layer_norm", "layer_norm_"),
                       ("cross_entropy_hard", "softmax_ce_")):
        mine = [r for r in recorded["table"] if r.program_op == op]
        assert {r.phase for r in mine} == {"forward", "backward"}
        kernels = sum(r.ms for r in mine if r.kernel)
        assert {r.kernel for r in mine if r.kernel} == \
            {k for k in TRAINING_KERNELS if k.startswith(prefix)}
        assert 0.8 * sum(r.ms for r in mine) < kernels


@pytest.mark.parametrize("prefix,cost,peak", [
    ("flash_", kernel_costs.flash_flops, "bf16_flops_per_s"),
    ("softmax_ce_", kernel_costs.hbm_bytes, "hbm_bytes_per_s")])
def test_roofline_shares_lie_between_0_and_100(recorded, prefix, cost, peak):
    share = kernel_costs.roofline_pct(recorded["table"], recorded["lines"],
                                      prefix, cost, PEAKS[peak])
    assert 0 < share <= 100
    assert kernel_costs.roofline_pct(recorded["table"], recorded["lines"],
                                     "no_such_", cost, PEAKS[peak]) is None


# ---- hand-made ---------------------------------------------------------------
TEXT = """HloModule jit_pure

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(pure)/backward/transpose(jvp(linear))/mul"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p.1)
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="batch[0]"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%a)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(pure)/forward/jvp(sdpa)/exp"}
  %flash_fwd.1 = (bf16[4,256,64]{2,1,0}, f32[4,256,1]{2,1,0}) custom-call(%x, %y, %z), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[4,256,64]{2,1,0}, bf16[4,512,64]{2,1,0}, bf16[4,512,64]{2,1,0}}, metadata={op_name="jit(pure)/forward/jvp(sdpa)/flash_fwd/pallas_call"}
  %flash_fwd_causal.2 = (bf16[4,512,64]{2,1,0:T(8,128)(2,1)}, f32[4,512,1]{2,1,0}) custom-call(%x, %y, %z), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[4,512,64]{2,1,0}, bf16[4,512,64]{2,1,0}, bf16[4,512,64]{2,1,0}}, metadata={op_name="jit(pure)/forward/jvp(sdpa)/flash_fwd_causal/pallas_call"}
  %add.1 = f32[8]{0} add(%fusion.3, %fusion.3), metadata={op_name="jit(pure)/optimizer/add"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) copy-start(%add.1)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  ROOT %tuple.1 = (f32[8]{0}) tuple(%copy-done.2)
}
"""
MOSAIC = " custom-call:tpu_custom_call"


def test_a_fusion_without_metadata_takes_its_roots():
    lines, roots = sr.instructions(TEXT)
    assert roots["fused_computation.1"] == "mul.9" and roots["main.1"] == "tuple.1"
    path = sr.op_name_of("fusion.1", lines, roots)
    assert path == "jit(pure)/backward/transpose(jvp(linear))/mul"
    assert sr.phase_of(path) == "backward"
    assert sr.program_op_of(path, REGISTERED) == "linear"
    assert sr.op_name_of("fusion.2", lines, roots) is None   # nor has its root
    assert sr.op_name_of("fusion.3", lines, roots).endswith("jvp(sdpa)/exp")


def test_data_movement_without_a_path_takes_its_neighbours():
    lines, roots = sr.instructions(TEXT)
    operands, users = sr.data_flow(lines)
    assert operands["fusion.1"] == ["copy-done.1"]
    assert users["copy-done.1"] == ["fusion.1"]
    # the wait before an op goes to the op that waits: its user
    paths = {name: sr.op_name_of(name, lines, roots) for name in lines}
    assert sr.nearest_path("copy-done.1", users, paths).endswith(
        "transpose(jvp(linear))/mul")
    # a copy into the outputs has no user with a phase: its producer
    assert sr.nearest_path("copy-done.2", users, paths) is None
    assert sr.nearest_path("copy-done.2", operands, paths) == \
        "jit(pure)/optimizer/add"
    table = sr.rows(*sr.instructions(TEXT), [("copy-done.1 copy-done", 0, 10),
                           ("copy-done.2 copy-done", 10, 30),
                           ("fusion.2 fusion", 30, 60),
                           ("a parameter", 60, 100)], 1, REGISTERED)
    got = {r.instruction: (r.phase, r.program_op, r.by_data_flow)
           for r in table}
    assert got == {"copy-done.1": ("backward", "linear", True),
                   "copy-done.2": ("optimizer", None, True),
                   # no path of its own: its user's (fusion.3, forward)
                   "fusion.2": ("forward", "sdpa", True),
                   # a path of its own that names no phase stays unscoped
                   "a": (None, None, False)}
    # a program that names no phase: nothing to take, everything unscoped
    bare = sr.rows(*sr.instructions(TEXT.replace("op_name=", "op_type=")),
                   [("copy-done.1 copy-done", 0, 10)], 1, REGISTERED)
    assert [(r.phase, r.by_data_flow) for r in bare] == [(None, False)]


def test_an_op_missing_from_the_text_is_counted_as_unscoped():
    events = [("fusion.1 fusion", 0, 100), ("fusion.2 fusion", 100, 150),
              ("gone.7 fusion", 150, 400), ("add.1 add", 400, 500),
              ("fusion.1 fusion", 500, 600)]
    table = sr.rows(*sr.instructions(TEXT), events, 2, REGISTERED)
    assert [r.label for r in table][0] == "gone.7 fusion"
    by_phase = sr.by(table, lambda r: r.phase)
    assert by_phase == pytest.approx(
        {"backward": 1e-4, "forward": 2.5e-5, None: 1.25e-4,
         "optimizer": 5e-5})
    assert sum(by_phase.values()) * 2 * 1e6 == pytest.approx(600)
    calls = {r.label: r.calls for r in table}
    assert calls["fusion.1 fusion"] == 1.0 and calls["gone.7 fusion"] == 0.5


@pytest.mark.parametrize("path,phase,op", [
    ("jit(pure)/forward/jvp(sdpa)/flash_fwd_causal/pallas_call", "forward", "sdpa"),
    ("jit(pure)/backward/transpose(forward)/jvp(sdpa)/flash_bwd_dq_causal/pallas_call", "backward", "sdpa"),
    ("jit(pure)/backward/transpose(jvp(linear))/transpose", "backward", "linear"),
    ("jit(pure)/backward/add", "backward", None),
    ("jit(pure)/forward/jvp(cross_entropy_hard)/jit(log_softmax)/sub", "forward", "cross_entropy_hard"),
    ("jit(pure)/forward/jvp(log_softmax)/sub", "forward", "log_softmax"),
    ("jit(pure)/forward/jvp(layer_norm)/shard_map/layer_norm_fwd/pallas_call", "forward", "layer_norm"),
    ("jit(pure)/optimizer/mul", "optimizer", None),
    ("jit(pure)/grad_exchange/psum", "grad_exchange", None),
    ("jit(pure)/forward/transpose", "forward", None),
    ("jit(pure)/convert_element_type", None, None),
    ("opt_state['w_0']['moment1']", None, None),
    ("", None, None), (None, None, None)])
def test_phase_and_program_op_of_a_path(path, phase, op):
    assert sr.phase_of(path) == phase
    assert sr.program_op_of(path, REGISTERED) == op


def test_self_time_gives_a_nested_ops_time_to_the_inner_op():
    events = [("while.1 while", 0, 1000), ("fusion.1 fusion", 100, 400),
              ("fusion.1 fusion", 500, 700), ("add.1 add", 1000, 1100)]
    assert sr.self_times(events) == {"while.1 while": [500, 1],
                                     "fusion.1 fusion": [500, 2],
                                     "add.1 add": [100, 1]}


def test_kernel_of_reads_mosaic_calls_only():
    assert sr.kernel_of("flash_fwd_causal.12" + MOSAIC) == "flash_fwd_causal"
    assert sr.kernel_of("softmax_ce_bwd" + MOSAIC) == "softmax_ce_bwd"
    assert sr.kernel_of("fusion.12 fusion") is None


def test_a_causal_flash_call_needs_half_the_plain_ones_flops():
    lines, _ = sr.instructions(TEXT)
    plain = kernel_costs.flash_flops("flash_fwd", lines["flash_fwd.1"])
    assert plain == 4 * 4 * 256 * 512 * 64              # Lq 256 x Lk 512
    causal = kernel_costs.flash_flops("flash_fwd_causal",
                                      lines["flash_fwd_causal.2"])
    assert causal == 4 * 4 * 512 * 512 * 64 * 513 / 1024
    line = lines["flash_fwd_causal.2"]
    together = kernel_costs.flash_flops("flash_bwd_dq_causal", line) + \
        kernel_costs.flash_flops("flash_bwd_dkv_causal", line)
    assert together == pytest.approx(2.5 * causal)
    # every operand and result once: 3 x bf16[4,512,64] in, one out, f32 lse
    assert kernel_costs.hbm_bytes("flash_fwd_causal", line) == \
        4 * (4 * 512 * 64 * 2) + 4 * 512 * 4
    table = sr.rows(*sr.instructions(TEXT),
                    [("flash_fwd_causal.2" + MOSAIC, 0, 2_000_000)], 1,
                    REGISTERED)
    share = kernel_costs.roofline_pct(table, lines, "flash_",
                                      kernel_costs.flash_flops, 197e12)
    assert share == pytest.approx(100 * causal / 197e12 / 2e-3)


# ---- the value head's width (PR 26) ------------------------------------------
def _dims(line):
    return [[int(d) for d in dims.split(",")] for dims in
            re.findall(r"\[([\d,]+)\]", kernel_costs.call_types(line)[0])]


def _flops_at_one_width(kernel, line):
    """``flash_flops`` as it was while every head had one width (PR 24)."""
    share = {"flash_fwd": 1.0, "flash_bwd_dq": 1.0, "flash_bwd_dkv": 1.5}
    causal = kernel.endswith("_causal")
    (bh, lq, d), (_, lk, _) = _dims(line)[:2]
    flops = share[kernel[:-len("_causal")] if causal else kernel] * \
        4.0 * bh * lq * lk * d
    return flops * (lk + 1) / (2.0 * lk) if causal else flops


@pytest.mark.parametrize("kernel", ["flash_fwd_causal", "flash_bwd_dq_causal",
                                    "flash_bwd_dkv_causal"])
def test_equal_head_widths_give_the_recorded_steps_flops_to_the_digit(
        recorded, kernel):
    rows = [r for r in recorded["table"] if r.kernel == kernel]
    assert rows
    for r in rows:
        line = recorded["lines"][r.instruction]
        assert kernel_costs.flash_flops(kernel, line) == \
            _flops_at_one_width(kernel, line)


def test_flash_flops_take_the_value_heads_width_from_the_v_operand():
    """Latent attention's shape: queries and keys of 128 + 64, values of 128.
    Counted at 192 throughout, the forward read 20% and the backward 15%
    high."""
    def line(name, out):
        return (f'  %{name}.1 = ({out}) custom-call(%q, %k, %v), '
                'custom_call_target="tpu_custom_call", '
                'operand_layout_constraints={bf16[8,4096,192]{2,1,0}, '
                'bf16[8,2048,192]{2,1,0}, bf16[8,2048,128]{2,1,0}}')
    scores = 2.0 * 8 * 4096 * 2048
    fwd = kernel_costs.flash_flops(
        "flash_fwd", line("flash_fwd", "bf16[8,4096,128]{2,1,0}, f32[8,1,4096]{2,1,0}"))
    dq = kernel_costs.flash_flops(
        "flash_bwd_dq", line("flash_bwd_dq", "bf16[8,4096,192]{2,1,0}"))
    dkv = kernel_costs.flash_flops(
        "flash_bwd_dkv", line("flash_bwd_dkv", "bf16[8,2048,192]{2,1,0}, bf16[8,2048,128]{2,1,0}"))
    assert fwd == scores * (192 + 128)                 # QK^T and PV
    assert dq == scores * (192 + (192 + 128) / 2)      # dQ, half of S and dP
    assert dkv == scores * (192 + 128 + (192 + 128) / 2)   # dK, dV, the rest
    assert dq + dkv == scores * (3 * 192 + 2 * 128)    # the five matmuls
    assert scores * 2 * 192 / fwd == pytest.approx(1.2)
    assert scores * 5 * 192 / (dq + dkv) == pytest.approx(1.1538, abs=1e-4)
    causal = kernel_costs.flash_flops(
        "flash_fwd_causal", line("flash_fwd_causal", "bf16[8,4096,128]{2,1,0}"))
    assert causal == fwd * 2049 / 4096
