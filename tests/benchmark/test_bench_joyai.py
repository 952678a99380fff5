"""The ``joyai`` family at a size a test can hold: the program's first three
steps through ``TrainStep`` (loss, first gradient, parameter change) against
``benchmark/reference/joyai.py`` with and without the multi-token-prediction
module, the fp8 control failing the same limits, a whole run; the share test
(four shares of four experts add up to the uncut layer); what the chip
configuration counts; the family's refusal of a program without the plain
residual; ``mtp_ms`` on a recorded text and on a tiny table."""
import copy
import os
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny
from benchmark import correct, harness, scope_paths, scope_reduce
from benchmark.loops import train
from benchmark.reference import _common as rc
from benchmark.reference import joyai as ref

SEED = 2 ** 31 + 35
RECORDED = os.path.join(os.path.dirname(__file__), "fixtures",
                        "joyai_step_mtp_lines.txt")

# Readings over four seeds of each preset, two of them over 2**31 (CPU, PR 35;
# program max / fp8 control min): grad_rel_err 0.0088 / 0.043, grad_norm_gap
# 0.016 / 0.021, loss gaps 1.1e-5 / 1.2e-6, delta_norm_gap 0.0065 / 0.0079.
# grad_rel_err's limit lies between its two readings and is the number the
# control must fail; the others sit three to five times over the program's
# largest (an unchanged state reads delta_norm_gap 1.0, rows left out move
# loss_gap_1 by far more). Routing is discrete: a token whose k-th and
# (k+1)-th scores lie closer than bfloat16's rounding of the hidden state
# changes experts between program and reference, which the gradient's limits
# leave room for.
LIMITS = {"loss_gap_1": 6e-5, "loss_gap_2": 6e-5, "loss_gap_3": 6e-5,
          "grad_norm_gap": 0.05, "grad_rel_err": 0.02, "delta_norm_gap": 0.03}


def tiny_cell(mtp=1):
    cfg = harness.load_json("configs", "joyai-llm-flash.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3, num_attention_heads=2, q_lora_rank=32,
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=4, n_routed_experts_published=8,
               first_routed_expert=2, num_experts_per_tok=2, vocab_size=512,
               num_nextn_predict_layers=mtp)
    traffic = harness.load_json("traffic", "packed_lm_8k_b1.json")
    traffic.update(batch=4, seq_len=64, pool_batches=4, eos_token=511)
    traffic["documents"]["median_len"] = 20
    return {"name": "tiny_joyai", "chips": 1, "loop": "train", "mesh": None,
            "limits": copy.deepcopy(LIMITS), "config": cfg,
            "traffic": traffic}


@pytest.fixture(scope="module", params=[0, 1], ids=["plain", "mtp"])
def readings(request):
    cell = tiny_cell(request.param)
    su = train.set_up(cell, SEED)
    got = train.program_readings(su.loop, su.model, su.step.optimizer,
                                 su.names, su.weights, su.index,
                                 cell["config"]["recipe"]["beta1"])
    batches = su.first_batches(train.CHECKED_STEPS, cell["traffic"]["batch"])

    def reference(precision):
        return train.reference_readings(su.family, cell, dict(su.weights),
                                        batches, su.index, precision)

    return got, reference("float32"), reference("fp8"), su, cell


def test_program_follows_the_reference(readings):
    got, want, _, _, _ = readings
    numbers = correct.compare(got, want)
    assert correct.judge(numbers, LIMITS), numbers


def test_fp8_control_is_not_correct(readings):
    _, want, control, _, _ = readings
    numbers = correct.compare(control, want)
    assert not correct.judge(numbers, LIMITS), numbers
    assert numbers["grad_rel_err"][0] > LIMITS["grad_rel_err"]


def test_every_parameter_is_compared_and_the_counter_ran(readings):
    got, want, _, su, cell = readings
    cfg = cell["config"]
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == \
        {name for name, _, _ in ref.param_specs(cfg)}
    assert not [n for n in got["grad_norms"] if "_hc." in n]
    assert all(np.isfinite(v) and v > 0 for v in want["grad_norms"].values())
    # every token chose k experts in every expert layer, the MTP block's last
    counts = su.model.expert_load_counts()
    assert counts.shape == (2 + cfg["num_nextn_predict_layers"], 8)
    assert (counts.sum(axis=1) == 4 * 64 * 2).all()
    # one signature for all three steps: no buffer changed its type on the
    # way (the loss's two terms stay in the bfloat16 the model was cast to)
    assert len(su.step._compiled) == 1
    held = su.family.expert_load(2)
    assert held.shape == (2, counts.shape[0], 4)
    assert (held[-1] == counts[:, 2:6]).all()


@pytest.mark.parametrize("mtp", [0, 1], ids=["plain", "mtp"])
def test_a_sound_run_is_correct(mtp):
    lines = []
    result = train.run(tiny_cell(mtp), bench_tiny.run_args(7),
                       time.perf_counter(), lines.append, lambda window: {})
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] >= 2


def test_a_step_that_leaves_the_mtp_loss_out_is_not_correct(monkeypatch):
    """Part of the mathematics left out underneath: the step trains on the
    main cross-entropy alone."""
    from paddle_tpu.models.nlp import latent_moe

    real = latent_moe.LatentMoEConfig.__init__

    def without(self, *a, **kw):
        real(self, *a, **dict(kw, mtp_lambda=0.0))

    monkeypatch.setattr(latent_moe.LatentMoEConfig, "__init__", without)
    lines = []
    result = train.run(tiny_cell(), bench_tiny.run_args(7),
                       time.perf_counter(), lines.append, lambda window: {})
    assert not result["correct"], lines


# ---- the share ------------------------------------------------------------------
def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """Sixteen experts, top-4, in four shares of four: the routed parts the
    four shares give (the program's ``ExpertMLP`` told which experts it
    holds) plus the shared expert, which every chip computes alike, counted
    once, are the uncut layer of the reference; and the reference's own
    shares add up the same. float32 on the CPU: 1e-5 of the output's scale,
    the order of sixteen float32 sums."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.nlp import latent_moe as lm

    cfg = tiny_cell()["config"]
    cfg.update(n_routed_experts=16, n_routed_experts_published=16,
               first_routed_expert=0, num_experts_per_tok=4)
    specs = [(n, s, i) for n, s, i in ref._layer_specs(cfg, "", False)
             if n.startswith("mlp.")]
    p = rc.init_weights(specs, 5, jnp.float32)
    p = {k: v * 8.0 for k, v in p.items()}     # outputs of order 1
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 24, 64)),
                    jnp.float32)
    mm = rc.matmul_of("float32")
    whole = ref.experts(cfg, p, x, mm)
    shared = ref.swiglu(x, p["mlp.shared.gate"], p["mlp.shared.up"],
                        p["mlp.shared.down"], mm)
    family = harness.load_module("families", "joyai")
    total, total_ref, slots = shared, shared, 0
    for first in (0, 4, 8, 12):
        share = dict(cfg, n_routed_experts=4, first_routed_expert=first)
        part = {k: (v[first:first + 4] if ".experts." in k else v)
                for k, v in p.items()}
        total_ref = total_ref + ref.routed_part(share, part, x, mm)
        layer = lm.ExpertMLP(family.program_config(dict(share, program={})))
        names = {"routed.router": "mlp.router",
                 "routed.experts_gate": "mlp.experts.gate",
                 "routed.experts_up": "mlp.experts.up",
                 "routed.experts_down": "mlp.experts.down"}
        for prog, param in layer.named_parameters():
            if prog in names:
                param.set_value(np.asarray(part[names[prog]]))
        y, load = layer.routed(Tensor(x, _internal=True))
        total = total + y._data
        slots += int(load.numpy()[first:first + 4].sum())
        assert load.numpy().sum() == 2 * 24 * 4    # it routes over all 16
    scale = float(jnp.abs(whole).max())
    assert scale > 0.1
    np.testing.assert_allclose(total, whole, atol=1e-5 * scale, rtol=1e-5)
    np.testing.assert_allclose(total_ref, whole, atol=1e-5 * scale, rtol=1e-5)
    assert slots == 2 * 24 * 4      # every slot landed in exactly one share
    # and one share alone is not the layer
    assert float(jnp.abs(ref.experts(
        dict(cfg, n_routed_experts=4), {k: (v[:4] if ".experts." in k else v)
                                        for k, v in p.items()}, x, mm)
        - whole).max()) > 0.05 * scale


# ---- the chip configuration -------------------------------------------------------
def test_the_chip_configuration_counts_as_its_file_says():
    cfg = harness.load_json("configs", "joyai-llm-flash.json")
    family = harness.load_module("families", "joyai")
    specs = family.reference.param_specs(cfg)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert total == 680_830_976 and f"{total:,}" in cfg["parameters"]
    assert cfg["num_nextn_predict_layers"] == 1 and "hc_mult" not in cfg
    assert cfg["n_routed_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_padded"] == 127 * 1024
    assert cfg["vocab_size"] % 128 == 0
    assert set(family.name_map(cfg).values()) == {n for n, _, _ in specs}
    # a position reaches 8 x 16 / 256 of one expert a layer in expectation,
    # in four layers and the MTP block; the head is read twice
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    head = cfg["vocab_size"] * cfg["hidden_size"]
    assert family.used_params(cfg) == pytest.approx(
        total - 5 * 16 * expert - head + head + 5 * 0.5 * expert)
    traffic = harness.load_json("traffic", "packed_lm_8k_b1.json")
    length = traffic["seq_len"]
    assert family.step_flops(cfg, traffic) == length * (
        6.0 * family.used_params(cfg) + 6.0 * 6 * 32 * 320 * length)
    # every published number of the source stands unless `reduced` names it
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_experts_per_tok": 8,
                 "n_shared_experts": 1, "first_k_dense_replace": 1,
                 "rope_theta": 32000000, "routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] is None and cfg["rope_interleave"] is True


def test_the_family_refuses_a_program_whose_one_stream_builds_maps(
        monkeypatch):
    from paddle_tpu.models.nlp import latent_moe

    family = harness.load_module("families", "joyai")
    family._require_plain_residual()
    real = latent_moe.LatentMoEBlock.__init__

    def with_maps(self, cfg, dense):
        real(self, cfg, dense)
        self.attn_hc = latent_moe.HyperConnection(cfg)

    monkeypatch.setattr(latent_moe.LatentMoEBlock, "__init__", with_maps)
    with pytest.raises(SystemExit, match="plain pre-norm residual"):
        family.build(tiny_cell()["config"], {}, None)


# ---- mtp_ms -----------------------------------------------------------------------
def test_instructions_under_the_scope_on_a_recorded_text():
    """Lines of the cell's own step as XLA:TPU compiled it for the described
    v5e (PR 35, ``tools/size_cells.py``'s compile): instructions of the MTP
    module and of the main stack, forward and backward, a fusion that takes
    its root's path and a copy that takes a neighbour's."""
    with open(RECORDED) as f:
        text = f.read()
    lines, roots = scope_reduce.instructions(text)
    under = scope_paths.instructions_under(text, "mtp")
    assert 0 < len(under) < len(lines)
    paths = {n: scope_reduce.op_name_of(n, lines, roots) for n in lines}
    with_path = [n for n in under if paths[n]]
    assert {scope_reduce.phase_of(paths[n]) for n in with_path} == \
        {"forward", "backward"}
    for name, path in paths.items():
        if path:
            assert (name in under) == ("mtp" in {
                s for s, _ in scope_reduce.scopes(path)[:-1]}), name
    # an instruction with no path of its own is under the scope with the
    # neighbour it moves data for
    moved = [n for n in lines if paths[n] is None and
             scope_reduce.nearest_path(
                 n, scope_reduce.data_flow(lines)[1], paths)]
    assert moved
    assert any(n in under for n in moved)
    assert scope_paths.instructions_under(text, "nosuchscope") == set()
    assert not scope_paths.holds("jit(pure)/forward/jvp(linear)/mtp", "mtp")
    assert scope_paths.holds(
        "jit(pure)/backward/transpose(jvp(mtp))/transpose(jvp(linear))/dot",
        "mtp")


def test_mtp_ms_on_a_tiny_table():
    text = (
        'ENTRY %main (p: f32[8]) -> f32[8] {\n'
        '  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(mtp)/jvp(linear_nobias)/dot_general"}\n'
        '  %b.2 = f32[8]{0} add(%a.1, %p), metadata={op_name="jit(pure)/'
        'backward/transpose(jvp(mtp))/transpose(jvp(rms_norm))/mul"}\n'
        '  %c.3 = f32[8]{0} add(%b.2, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(linear_nobias)/dot_general"}\n'
        '  ROOT %d.4 = f32[8]{0} add(%c.3, %p), metadata={op_name="jit(pure)/'
        'optimizer/mul"}\n}\n')

    def row(instruction, phase, ms):
        return scope_reduce.Row(instruction + " fusion", phase, None, None,
                                ms, 1.0, False)

    table = [row("a.1", "forward", 2.0), row("b.2", "backward", 3.0),
             row("c.3", "forward", 5.0), row("d.4", "optimizer", 7.0),
             row("gone.9", None, 11.0)]
    window = types.SimpleNamespace(compiled_text=text)
    window.scope_table = (table, {})
    reader = harness.load_module("layer_metrics", "mtp_ms")
    assert reader.read(window) == 5.0
    assert (reader.LAYER, reader.UNIT) == ("multi-token prediction", "ms")
    # a step with nothing under the scope reads 0, not nothing: the metric
    # is owed in every training cell
    window.compiled_text = text.replace("mtp", "aux")
    assert reader.read(window) == 0.0
    # a program that names no phase has nothing to read
    window.scope_table = (None, {})
    assert reader.read(window) is None


def test_the_new_cell_is_owed_the_metrics_of_its_rules_and_no_residual_mix():
    man = harness.manifest()
    cell = harness.load_cell("joyai_pretrain_mtp_ep16", man)
    reported = {m["name"] for m, _ in harness.layer_readers(man, cell)}
    assert {"mtp_ms", "rms_norm_ms", "expert_matmul_ms", "expert_dispatch_ms",
            "expert_load_max_over_mean", "expert_roofline_pct",
            "flash_roofline_pct", "softmax_ce_roofline_pct"} <= reported
    assert not reported & {"residual_mix_ms", "collective_mb",
                           "collective_exposed_ms"}
    assert cell["traffic"]["eos_token"] == cell["config"]["vocab_size"] - 1
    assert train.micro_rows(cell["traffic"]["seq_len"]) == 1
