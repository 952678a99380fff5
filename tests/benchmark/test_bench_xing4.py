"""The ``xing4`` family at a size a test can hold: the program's first three
steps through ``TrainStep`` (loss, first gradient, parameter change) against
``benchmark/reference/xing4.py`` as ``test_bench_correct.py`` does for the two
older families, with and without the multi-token-prediction module; the fp8
control failing the same limits; a whole run; the share test's model half
(the eight shares' parameter counts); the new readers on a tiny table."""
import copy
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark import correct, expert_costs, harness
from benchmark.loops import train

SEED = 2 ** 31 + 27

# Readings over four seeds of each preset (CPU, PR 27; program max / fp8
# control min): grad_rel_err 0.0094 / 0.0409, grad_norm_gap 0.019 / 0.032,
# loss gaps 4.5e-5 / 1e-6, delta_norm_gap 0.20 / 0.015. grad_rel_err's limit
# lies between its two readings and is the number the control must fail;
# the others sit about three times over the program's largest. Routing is
# discrete: a token whose k-th and (k+1)-th scores lie closer than
# bfloat16's rounding of the hidden state changes experts between program
# and reference, which the gradient's limits leave room for.
LIMITS = {"loss_gap_1": 1.5e-4, "loss_gap_2": 1.5e-4, "loss_gap_3": 1.5e-4,
          "grad_norm_gap": 0.06, "grad_rel_err": 0.02, "delta_norm_gap": 0.6}


def tiny_cell(mtp=0):
    cfg = harness.load_json("configs", "xing4.0-29b-a4b.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3, first_k_dense_replace=1,
               num_attention_heads=2, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=4, n_routed_experts_published=8,
               first_routed_expert=2, num_experts_per_tok=2, vocab_size=512,
               num_nextn_predict_layers=mtp, hc_alpha_init=0.5)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16, factor=4)
    traffic = harness.load_json("traffic", "packed_lm_4k_b1.json")
    traffic.update(batch=4, seq_len=64, pool_batches=4, eos_token=511)
    traffic["documents"]["median_len"] = 20
    return {"name": "tiny_xing4", "chips": 1, "loop": "train", "mesh": None,
            "limits": copy.deepcopy(LIMITS), "config": cfg,
            "traffic": traffic}


def _readings(cell, seed):
    su = train.set_up(cell, seed)
    got = train.program_readings(su.loop, su.model, su.step.optimizer,
                                 su.names, su.weights, su.index,
                                 cell["config"]["recipe"]["beta1"])
    batches = su.first_batches(train.CHECKED_STEPS, cell["traffic"]["batch"])

    def reference(precision):
        return train.reference_readings(su.family, cell, dict(su.weights),
                                        batches, su.index, precision)

    return got, reference, su


@pytest.fixture(scope="module", params=[0, 1], ids=["plain", "mtp"])
def readings(request):
    got, reference, su = _readings(tiny_cell(request.param), SEED)
    return got, reference("float32"), reference("fp8"), su


def test_program_follows_the_reference(readings):
    got, want, _, _ = readings
    numbers = correct.compare(got, want)
    assert correct.judge(numbers, LIMITS), numbers


def test_fp8_control_is_not_correct(readings):
    _, want, control, _ = readings
    numbers = correct.compare(control, want)
    assert not correct.judge(numbers, LIMITS), numbers
    assert numbers["grad_rel_err"][0] > LIMITS["grad_rel_err"]


def test_the_bfloat16_witness_stays_inside_the_limits(readings):
    """The reference with bfloat16 operands (``tools/calibrate.py
    --witness-seeds``) is the stated precision, not one below it: it differs
    from the float32 reference and every limit holds it."""
    _, want, _, su = readings
    cell = tiny_cell(int(su.model.mtp is not None))
    witness = train.reference_readings(
        su.family, cell, dict(su.weights),
        su.first_batches(train.CHECKED_STEPS, cell["traffic"]["batch"]),
        su.index, "bfloat16")
    numbers = correct.compare(witness, want)
    assert correct.judge(numbers, LIMITS), numbers
    assert numbers["grad_rel_err"][0] > 1e-4


def test_every_parameter_is_compared_and_every_buffer_left_out(readings):
    got, want, _, su = readings
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == \
        {name for name, _, _ in su.family.reference.param_specs(
            su.model and tiny_cell(int(su.model.mtp is not None))["config"])}
    assert all(np.isfinite(v) and v > 0 for v in want["grad_norms"].values())
    # the counter ran in the compiled step: every token chose k experts
    counts = su.model.expert_load_counts()
    assert counts.shape[1] == 8 and (counts.sum(axis=1) == 4 * 64 * 2).all()
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


def test_a_step_without_the_shared_expert_is_not_correct(monkeypatch):
    """Part of the mathematics left out underneath: the shared expert's
    output never joins the routed experts'."""
    from paddle_tpu.models.nlp import latent_moe

    monkeypatch.setattr(latent_moe.ExpertMLP, "forward",
                        lambda self, x: self.routed(x))
    lines = []
    result = train.run(tiny_cell(), bench_tiny.run_args(7),
                       time.perf_counter(), lines.append, lambda window: {})
    assert not result["correct"], lines


def test_a_lost_gradient_leaf_is_outside_the_chip_cells_grad_norm_limit(
        monkeypatch):
    """The timed path broken underneath: the routed experts' gate and up
    projections never get their gradients. The chip cell's ``grad_norm_gap``
    limit no longer judges precision (the routers' gradients pass through a
    discrete choice; PERF.md section 2) and is held for this: at the cell's
    own value those leaves come out OUTSIDE."""
    from paddle_tpu import optim

    cell = tiny_cell()
    cell["limits"]["grad_norm_gap"] = harness.load_json(
        "workloads", "xing4_pretrain_ep8.json")["limits"]["grad_norm_gap"]
    cfg = cell["config"]
    lost = (cfg["n_routed_experts"], cfg["hidden_size"],
            cfg["moe_intermediate_size"])
    update = optim.AdamW._update
    monkeypatch.setattr(
        optim.AdamW, "_update", lambda self, p, g, s, lr: update(
            self, p, g * 0 if g.shape == lost else g, s, lr))
    lines = []
    result = train.run(cell, bench_tiny.run_args(7), time.perf_counter(),
                       lines.append, lambda window: {})
    assert not result["correct"], lines
    assert any(l.startswith("check grad_norm_gap") and "OUTSIDE" in l and
               ".mlp.experts." in l for l in lines), lines


def test_the_chip_configuration_counts_as_its_file_says():
    cfg = harness.load_json("configs", "xing4.0-29b-a4b.json")
    family = harness.load_module("families", "xing4")
    specs = family.reference.param_specs(cfg)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert f"{total:,}" in cfg["parameters"]
    assert 0.75e9 < total < 0.77e9
    # a position reaches 4 x 8 / 64 of one expert a layer in expectation
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    routed = 4 * cfg["n_routed_experts"] * expert
    assert family.used_params(cfg) == pytest.approx(
        total - routed - cfg["vocab_size"] * cfg["hidden_size"]
        + 4 * 0.5 * expert)
    assert set(family.name_map(cfg).values()) == {n for n, _, _ in specs}


# ---- the readers on a tiny table of rows --------------------------------------
def _window(table, counts, cell):
    window = types.SimpleNamespace(
        cell=cell, trace=types.SimpleNamespace(steps=len(counts)),
        family=types.SimpleNamespace(expert_load=lambda steps: counts[-steps:]))
    window.scope_table = (table, {})
    return window


def test_the_new_readers_on_a_tiny_table(monkeypatch):
    from benchmark import scope_reduce

    def row(op, ms):
        return scope_reduce.Row(op + ".1 fusion", "forward", op, None, ms,
                                1.0, False)

    table = [row("moe_experts", 2.0), row("moe_experts", 1.0),
             row("moe_route", 0.5), row("moe_plan", 0.25),
             row("moe_dispatch", 0.5), row("moe_combine", 0.75),
             row("hc_maps", 1.0), row("hc_read", 0.5), row("hc_mix", 0.5),
             row("rms_norm", 0.125), row("linear_nobias", 9.0)]
    counts = np.array([[[10, 30], [20, 20]], [[30, 10], [20, 20]]])
    cell = {"config": {"n_routed_experts": 2, "hidden_size": 8,
                       "moe_intermediate_size": 4, "hc_mult": 4,
                       "rms_norm_eps": 1e-6}, "name": "t"}
    window = _window(table, counts, cell)
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1e9})

    def read(name):
        reader = harness.load_module("layer_metrics", name)
        assert reader.reports(cell) and not reader.reports(
            {"config": {"n_layer": 2}})
        return reader.read(window)

    assert read("expert_matmul_ms") == 3.0
    assert read("expert_dispatch_ms") == 2.0
    assert read("residual_mix_ms") == 2.0
    assert read("rms_norm_ms") == 0.125
    # (30/20 + 20/20 + 30/20 + 20/20) / 4
    assert read("expert_load_max_over_mean") == pytest.approx(1.25)
    # 80 slots a step x 3 x 2 x 3 x 8 x 4 FLOPs over 1e9 FLOP/s, in 3 ms
    assert read("expert_roofline_pct") == pytest.approx(
        100.0 * 80 * 576 / 1e9 / 3e-3)
    assert expert_costs.slot_flops(3584, 1024) == 3 * 2 * 3 * 3584 * 1024
    # a model that is gone, or a family without the counter: nothing to read
    window.family = types.SimpleNamespace(expert_load=lambda steps: None)
    assert read("expert_load_max_over_mean") is None
    assert read("expert_roofline_pct") is None
    window.family = types.SimpleNamespace()
    assert read("expert_roofline_pct") is None
