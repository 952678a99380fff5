"""The ``laguna`` family at a size a test can hold: the program's first three
steps through ``TrainStep`` (loss, first gradient, parameter change) against
``benchmark/reference/laguna.py``, the fp8 control failing a limit; what the
chip configuration counts; the family's refusal of a program without the
model; the cell's own rows from its seed. Its layers and readers alone:
``test_bench_laguna_layers.py``."""
import copy
import importlib.util

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import correct, harness
from benchmark.loops import train
from benchmark.reference import laguna as ref

SEED = 2 ** 31 + 37
CELL = "laguna_pretrain_swa_ep32"

# Readings over four seeds, two of them over 2**31 (CPU, PR 42, at five
# layers; program max / fp8 control min): grad_rel_err 0.0108 / 0.050,
# grad_norm_gap 0.017 / 0.016, delta_norm_gap 0.0057 / 0.012, loss gaps
# 3.1e-5 / 9e-6. grad_rel_err's limit lies between its two readings with room
# on both sides and is the number the control must fail; the others sit about
# three times over the program's largest (an unchanged state reads
# delta_norm_gap 1.0, rows left out move loss_gap_1 by far more). Routing is
# discrete: a token whose k-th and (k+1)-th scores lie closer than bfloat16's
# rounding of the hidden state changes experts between program and reference,
# which the gradient's limits leave room for.
LIMITS = {"loss_gap_1": 1e-4, "loss_gap_2": 1e-4, "loss_gap_3": 1e-4,
          "grad_norm_gap": 0.05, "grad_rel_err": 0.025, "delta_norm_gap": 0.02}


def tiny_config(**kw):
    """Three layers (dense + full, then two sparse + sliding), 4 and 6 heads
    over 2 key/value heads, a window of 8, experts 2-5 of 8."""
    cfg = harness.load_json("configs", "laguna-s-2.1.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, head_dim=16,
               num_hidden_layers=3, num_key_value_heads=2,
               num_attention_heads=4, num_attention_heads_per_layer=[4, 6, 6],
               layer_types=cfg["layer_types"][:3],
               mlp_layer_types=cfg["mlp_layer_types"][:3],
               gating_types=cfg["gating_types"][:3], sliding_window=8,
               num_experts=4, n_routed_experts=4, num_experts_published=8,
               first_routed_expert=2, num_experts_per_tok=2, vocab_size=512)
    cfg["rope_parameters"] = copy.deepcopy(cfg["rope_parameters"])
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cfg.update(kw)
    return cfg


def tiny_cell():
    traffic = harness.load_json("traffic", "packed_lm_8k_b1_v12544.json")
    traffic.update(batch=4, seq_len=40, pool_batches=4, eos_token=511)
    traffic["documents"]["median_len"] = 20
    return {"name": "tiny_laguna", "chips": 1, "loop": "train", "mesh": None,
            "limits": copy.deepcopy(LIMITS), "config": tiny_config(),
            "traffic": traffic}


@pytest.fixture(scope="module")
def readings():
    cell = tiny_cell()
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


def test_every_parameter_is_compared_and_the_counters_ran(readings):
    got, want, _, su, cell = readings
    cfg = cell["config"]
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == \
        {name for name, _, _ in ref.param_specs(cfg)}
    assert all(np.isfinite(v) and v > 0 for v in want["grad_norms"].values())
    # every token chose k experts in both sparse layers
    counts = su.model.expert_load_counts()
    assert counts.shape == (2, 8)
    assert (counts.sum(axis=1) == 4 * 40 * 2).all()
    held = su.family.expert_load(2)
    assert held.shape == (2, 2, 4) and (held[-1] == counts[:, 2:6]).all()
    # one signature for all three steps: no buffer changed its type on the way
    assert len(su.step._compiled) == 1
    # seeded gates open about half; a window of 8 keeps 292 of the 820
    # pairs of a row of 40 (the buffer is bfloat16, as the model)
    gate, share = (float(x) for x in su.model.attn_stats._data)
    assert 0.45 < gate < 0.55 and share == pytest.approx(292 / 820, rel=4e-3)
    # both kinds of sublayer name their work, forward and backward
    text = su.step.compiled().as_text()
    for scope in ("window_attn", "gqa_attn"):
        assert f"jvp({scope})" in text and f"transpose(jvp({scope}))" in text \
            or f"/{scope}/" in text, scope


# ---- the chip configuration -------------------------------------------------------
def test_the_chip_configuration_counts_as_its_file_says():
    cfg = harness.load_json("configs", "laguna-s-2.1.json")
    family = harness.load_module("families", "laguna")
    specs = family.reference.param_specs(cfg)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert total == 811_017_216 and f"{total:,}" in cfg["parameters"]
    sizes = {n: int(np.prod(s)) for n, s, _ in specs}

    def under(prefix):
        return sum(v for n, v in sizes.items() if n.startswith(prefix))

    assert under("layers.0.attn.") == under("layers.4.attn.") == 44_187_648
    assert under("layers.1.attn.") == 63_135_744
    assert under("layers.1.") - under("layers.1.attn.") == 85_727_232
    assert under("layers.0.") == 157_440_000
    assert under("layers.4.") == 129_914_880
    assert set(family.name_map(cfg).values()) == set(sizes)
    pcfg = family.program_config(cfg)
    assert pcfg.heads_per_layer == (48, 72, 72, 72, 48)
    assert pcfg.layer_types == ("full_attention",) + \
        ("sliding_attention",) * 3 + ("full_attention",)
    assert (pcfg.kv_heads, pcfg.head_dim, pcfg.window) == (8, 128, 512)
    assert (pcfg.first_dense, pcfg.dense_width) == (1, 12288)
    assert (pcfg.experts, pcfg.experts_held, pcfg.top_k, pcfg.routed_scale,
            pcfg.shared_experts) == (256, 8, 10, 2.5, 1)
    assert pcfg.router_score == "softmax" and pcfg.use_recompute
    assert pcfg.rope_of("full_attention") == (
        64, 500000.0, {"factor": 128, "beta_fast": 32, "beta_slow": 1,
                       "original_max_position_embeddings": 8192},
        1.4852030263919618)
    assert pcfg.rope_of("sliding_attention") == (128, 10000.0, None, None)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["vocab_size"] % 128 == 0
    # the key the expert readers ask for repeats the experts held
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 8
    # a position reaches 8 x 10 / 256 of one expert a sparse layer
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    assert family.used_params(cfg) == pytest.approx(
        total - embed - 4 * 8 * expert + 4 * (80 / 256) * expert)
    cell = harness.load_cell(CELL)
    length = cell["traffic"]["seq_len"]
    # no pair the band removes is counted: 512 keys a windowed head
    assert family.step_flops(cfg, cell["traffic"]) == length * (
        6.0 * family.used_params(cfg) +
        12.0 * 128 * (2 * 48 * length + 3 * 72 * 512))


def test_every_published_number_stands_unless_reduced_names_it():
    """The catalog's row (``model-configs`` guide, Laguna-S-2.1) as it was
    read for PR 42: every key under its own name, changed only where
    ``reduced`` says so; a list a layer is cut to its first five entries."""
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": kinds * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    cfg = harness.load_json("configs", "laguna-s-2.1.json")
    entry = [c for c in harness.manifest()["configs"]
             if c["name"] == "laguna-s-2.1"][0]
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"]) == \
        set(cfg["changed"])
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == published[key][:5], key
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert cfg[key + "_published"] == published[key], key
    # no width among them
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and
                k != "vocab_size"]
    assert entry["source"] == cfg["source"]


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    real = importlib.util.find_spec

    def absent(name, *a, **kw):
        return None if name.endswith("laguna_moe") else real(name, *a, **kw)

    monkeypatch.setattr(importlib.util, "find_spec", absent)
    with pytest.raises(SystemExit, match="laguna_moe"):
        harness.load_module("families", "laguna")


def test_the_new_cell_is_owed_the_metrics_of_its_rules():
    man = harness.manifest()
    cell = harness.load_cell(CELL, man)
    reported = {m["name"] for m, _ in harness.layer_readers(man, cell)}
    assert {"window_attention_ms", "swa_roofline_pct", "attention_ms",
            "mtp_ms", "linear_attention_ms", "rms_norm_ms", "mfu_pct",
            "expert_matmul_ms", "expert_dispatch_ms",
            "expert_load_max_over_mean", "expert_roofline_pct",
            "flash_roofline_pct", "softmax_ce_roofline_pct"} <= reported
    assert not reported & {"residual_mix_ms", "collective_mb",
                           "collective_exposed_ms"}
    assert cell["kernels"] == ["flash_", "swa_", "softmax_ce_"]
    # every other training cell is owed the two new readers too (they read 0)
    for other in man["workloads"]:
        names = {m["name"] for m, _ in harness.layer_readers(
            man, harness.load_cell(other["name"], man))}
        assert {"window_attention_ms", "swa_roofline_pct"} <= names, other
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["traffic"]["eos_token"] == cell["config"]["vocab_size"] - 1
    # one packed row of 8,192 tokens is a micro-batch of the reference, and
    # the windowed layers' blocks divide it
    assert cell["traffic"]["seq_len"] == 8192 == 16 * ref.QUERY_BLOCK
    assert train.micro_rows(cell["traffic"]["seq_len"]) == 1


def test_the_two_readers_were_appended_after_the_eight_of_start_up():
    """``BENCHMARK.json`` grows at the end of its lists: the two readers of
    PR 42 follow PR 40's eight start-up metrics, which stand together and in
    their order (``conftest.py`` says why ``test_bench_startup.py``'s own
    assertion sees the list up to them)."""
    names = [m["name"] for m in harness.manifest()["per_layer"]]
    eight = ["import_s", "param_init_s", "step_lower_s", "step_load_s",
             "step_first_execute_s", "small_programs_s", "cache_misses",
             "setup_unnamed_s"]
    at = names.index("import_s")
    assert names[at:at + 8] == eight
    assert names[at + 8:] == ["window_attention_ms", "swa_roofline_pct"]
    config, cell = harness.manifest()["configs"][-1], \
        harness.manifest()["workloads"][-1]
    assert (config["name"], cell["name"]) == ("laguna-s-2.1", CELL)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5])
def test_the_cells_own_rows_come_from_the_seed_and_the_vocabulary_slice(seed):
    from benchmark import generate

    cell = harness.load_cell(CELL)
    traffic = dict(cell["traffic"], pool_batches=2)
    vocab = cell["config"]["vocab_size"]
    ids, labels = generate.pool(traffic, vocab, seed)
    assert ids.shape == labels.shape == (2, 8192)
    assert 0 <= ids.min() and ids.max() < vocab == 12544
    assert (ids == traffic["eos_token"]).any()    # documents end inside rows
    again, _ = generate.pool(traffic, vocab, seed)
    other, _ = generate.pool(traffic, vocab, seed + 1)
    assert (ids == again).all() and (ids != other).any()
