"""The ``lfm2`` family: what the chip configuration counts and states, row by
row; at a size a test can hold, the program's first three steps through
``TrainStep`` in bfloat16 (loss, first gradient, parameter change) against
``benchmark/reference/lfm2.py``, the fp8 control failing a limit; the program
scopes and the new op on the compiled step; the family's refusal of a program
without the model; the cell's own rows from its seed; ``conv_costs`` by hand
and the three readers on a tiny table, their rules on two synthetic cells;
where this PR's entries stand in the manifest. The model in float32 against
the reference and the share test: ``tests/test_lfm2_moe.py``; the op alone:
``tests/test_gated_short_conv.py``."""
import copy
import importlib.util
import types

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import conv_costs, correct, harness, scope_paths, scope_reduce
from benchmark.loops import train
from benchmark.reference import lfm2 as ref

SEED = 2 ** 31 + 48
CELL = "lfm2_pretrain_conv_ep4"
CONFIG = "lfm2-8b-a1b"

# Readings over four seeds, two of them over 2**31 (CPU, PR 48, at three
# layers; program max / fp8 control min): grad_rel_err 0.0062 / 0.0520,
# grad_norm_gap 0.0166 / 0.0223, delta_norm_gap 0.0036 / 0.0089, loss gaps
# 2.4e-5 / 5e-6. grad_rel_err's limit lies between its two readings with room
# on both sides (2.9x over the one, 2.9x under the other) and is the number
# the control must fail; the others sit three times over the program's
# largest, since their two readings touch (an unchanged state reads
# delta_norm_gap 1.0, rows left out move loss_gap_1 by far more; bfloat16's
# rounding of a loss of 6.2 is itself 2e-6 of it, and a near-tie in the
# router that falls the other way moves a loss by 2e-5).
LIMITS = {"loss_gap_1": 8e-5, "loss_gap_2": 8e-5, "loss_gap_3": 8e-5,
          "grad_norm_gap": 0.05, "grad_rel_err": 0.018,
          "delta_norm_gap": 0.011}


def tiny_config(**kw):
    """Three layers (a dense convolution layer, attention and a convolution
    over experts), 4 query heads over 2 key/value heads of 16, top-2 of 8
    experts of which the first 4 are held."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3,
               layer_types=["conv", "full_attention", "conv"],
               num_attention_heads=4, num_key_value_heads=2, num_experts=4,
               num_experts_published=8, num_experts_per_tok=2,
               n_routed_experts=4, vocab_size=512)
    cfg.update(kw)
    return cfg


def tiny_cell():
    traffic = harness.load_json("traffic", "packed_lm_8k_b4_v16384.json")
    traffic.update(batch=4, seq_len=40, pool_batches=4, eos_token=511)
    traffic["documents"]["median_len"] = 20
    return {"name": "tiny_lfm2", "chips": 1, "loop": "train",
            "mesh": None, "limits": copy.deepcopy(LIMITS),
            "config": tiny_config(), "traffic": traffic}


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


def test_every_parameter_is_compared_and_the_step_names_its_work(readings):
    got, want, _, su, cell = readings
    cfg = cell["config"]
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == \
        {name for name, _, _ in ref.param_specs(cfg)}
    assert "head" not in got["grad_norms"]          # one tied leaf
    assert all(np.isfinite(v) and v > 0 for v in want["grad_norms"].values())
    # one signature for all three steps: no buffer changed its type on the way
    assert len(su.step._compiled) == 1
    # the family's counter: the 4 held of 8 experts, two expert layers
    load = su.family.expert_load(3)
    assert load.shape == (3, 2, 4) and load.sum() > 0
    assert su.model.expert_load_counts().sum(axis=1).tolist() == [320, 320]
    # both kinds of sublayer name their work, forward and backward; the
    # gated convolution is a program op under its sublayer's scope alone
    text = su.step.compiled().as_text()
    paths = set(scope_reduce._OP_NAME.findall(text))
    from paddle_tpu.ops import OP_REGISTRY
    wanted = {"short_conv": {"linear_nobias", "gated_short_conv"},
              "gqa_attn": {"linear_nobias", "rms_norm", "rotary", "sdpa"}}
    for scope, ops in wanted.items():
        mine = [p for p in paths if scope_paths.holds(p, scope)]
        assert {scope_reduce.phase_of(p) for p in mine} == \
            {"forward", "backward"}, scope
        for phase in ("forward", "backward"):
            named = {name for p in mine if scope_reduce.phase_of(p) == phase
                     for name, _ in scope_reduce.scopes(p)[:-1]}
            assert ops <= named, (scope, phase, ops - named)
    conv = [p for p in paths if scope_reduce.program_op_of(
        p, set(OP_REGISTRY)) == "gated_short_conv"]
    assert conv and all(scope_paths.holds(p, "short_conv") for p in conv)
    assert {scope_reduce.phase_of(p) for p in conv} == {"forward", "backward"}
    # the scope's name is a registered op's too (the SiLU convolution, which
    # this model does not call): no instruction's innermost op is that one
    assert "short_conv" in OP_REGISTRY
    assert not [p for p in paths if scope_reduce.program_op_of(
        p, set(OP_REGISTRY)) == "short_conv"]
    # the MLPs, the experts and the head are under neither scope
    for op in ("swiglu", "moe_route", "cross_entropy_hard", "embedding",
               "matmul"):
        inside = [p for p in paths if op in {
            n for n, _ in scope_reduce.scopes(p)}]
        assert inside, op
        assert not [p for p in inside if scope_paths.holds(p, "short_conv") or
                    scope_paths.holds(p, "gqa_attn")], op


# ---- the chip configuration --------------------------------------------------
def test_the_chip_configuration_counts_as_its_file_says():
    cfg = harness.load_json("configs", CONFIG + ".json")
    family = harness.load_module("families", "lfm2")
    specs = family.reference.param_specs(cfg)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert total == 507_820_160 and f"{total:,}" in cfg["parameters"]
    sizes = {n: int(np.prod(s)) for n, s, _ in specs}

    def under(prefix):
        return sum(v for n, v in sizes.items() if n.startswith(prefix))

    # the issue's table, row by row
    assert sizes["layers.0.conv.in_proj"] == 2048 * 6144 == 12_582_912
    assert sizes["layers.0.conv.taps"] == 3 * 2048 == 6_144
    assert sizes["layers.0.conv.out_proj"] == 4_194_304
    assert under("layers.0.conv.") == under("layers.2.conv.") == 16_783_360
    assert sizes["layers.1.attn.q"] == sizes["layers.1.attn.o"] == 4_194_304
    assert sizes["layers.1.attn.k"] == sizes["layers.1.attn.v"] == 1_048_576
    assert sizes["layers.1.attn.q_norm"] == sizes["layers.1.attn.k_norm"] == 64
    assert under("layers.1.attn.") == 10_485_888
    for i in range(5):
        assert sizes[f"layers.{i}.op_norm"] + sizes[f"layers.{i}.ffn_norm"] \
            == 4_096
    assert under("layers.0.mlp.") == 3 * 2048 * 7168 == 44_040_192
    assert sizes["layers.1.mlp.router"] == 2048 * 32 == 65_536
    assert under("layers.1.mlp.experts.") == 8 * 3 * 2048 * 1792 == \
        8 * 11_010_048
    assert under("layers.1.mlp.") == under("layers.4.mlp.") == 88_145_920
    assert under("layers.0.") == 60_827_648
    assert under("layers.1.") == 98_635_904
    assert under("layers.2.") == under("layers.3.") == under("layers.4.") == \
        104_933_376
    assert under("layers.") == 474_263_680
    assert sizes["embed"] == 16384 * 2048 == 33_554_432
    assert sizes["norm"] == 2_048 and "head" not in sizes
    for number in (16_783_360, 10_485_888, 44_040_192, 88_145_920, 60_827_648,
                   98_635_904, 104_933_376, 474_263_680, 33_554_432):
        assert f"{number:,}" in cfg["parameters"], number
    assert set(family.name_map(cfg).values()) == set(sizes)
    pcfg = family.program_config(cfg)
    assert pcfg.layer_types == ("conv", "full_attention", "conv", "conv",
                                "conv")
    assert (pcfg.heads, pcfg.kv_heads, pcfg.head_dim, pcfg.rope_theta) == \
        (32, 8, 64, 1e6)
    assert (pcfg.conv_size, pcfg.dense_layers, pcfg.dense_width) == \
        (3, 1, 7168)
    assert (pcfg.experts, pcfg.experts_held, pcfg.first_expert, pcfg.top_k,
            pcfg.expert_width, pcfg.routed_scale, pcfg.norm_topk) == \
        (32, 8, 0, 4, 1792, 1, True)
    assert pcfg.router_score == "sigmoid" and pcfg.shared_experts == 0
    assert pcfg.tie_head and pcfg.use_recompute and pcfg.rms_eps == 1e-5
    assert cfg["vocab_size"] * 4 == cfg["vocab_size_published"]
    assert cfg["vocab_size"] == 128 * 128
    # the two aliases the readers' rules ask for say what the source's keys say
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 8
    assert cfg["rms_norm_eps"] == cfg["norm_eps"] == 1e-5
    assert len(cfg["n_routed_experts_why"]) > 100 and \
        len(cfg["rms_norm_eps_why"]) > 100
    cell = harness.load_cell(CELL)
    length, batch = cell["traffic"]["seq_len"], cell["traffic"]["batch"]
    # every leaf once (the tied matrix as the head), a held expert at its
    # reach of 4 / 32, 32 heads of 64 + 64 over the whole row in one layer
    experts = 4 * 8 * 11_010_048
    assert family.used_params(cfg) == total - experts + experts * 4 / 32 == \
        199_538_816
    assert family.step_flops(cfg, cell["traffic"]) == batch * length * (
        6.0 * 199_538_816 + 6.0 * 32 * 128 * length)
    assert 45e12 < family.step_flops(cfg, cell["traffic"]) < 46e12
    # a program that cannot be this model is refused, not approximated
    with pytest.raises(ValueError, match="tied"):
        family.program_config(dict(cfg, tie_word_embeddings=False))
    with pytest.raises(ValueError, match="embedding"):
        ref.param_specs(dict(cfg, conv_bias=True))


def test_every_published_number_stands_unless_reduced_names_it():
    """The catalog's row (``model-configs`` guide, LFM2-8B-A1B) as it was read
    for PR 48: every key under its own name, changed only where ``reduced``
    says so; the list a layer is cut to the published entries 1-5."""
    c, a = "conv", "full_attention"
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": [c, c, a, c, c, c, a, c, c, c, a, c, c, c, a, c, c, c,
                        a, c, c, a, c, c],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert len(published["layer_types"]) == 24 and \
        published["layer_types"].count(a) == 6
    cfg = harness.load_json("configs", CONFIG + ".json")
    entry = [c for c in harness.manifest()["configs"]
             if c["name"] == CONFIG][0]
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"]) == \
        set(cfg["changed"]) == {"num_hidden_layers", "layer_types",
                                "num_dense_layers", "num_experts",
                                "vocab_size"}
    assert cfg["layer_types"] == published["layer_types"][1:6]
    for key in ("num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"):
        assert cfg[key + "_published"] == published[key], key
    assert cfg["first_routed_expert"] == 0
    assert entry["source"] == cfg["source"]
    # the floors: a leading dense layer and a whole period of four after it,
    # 8 routed experts, a quarter (at least an eighth) of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 4
    assert cfg["layer_types"][1:].count(a) * 3 == \
        cfg["layer_types"][1:].count(c)
    # what the source does not give is said, each with its reason
    assert {"tie_word_embeddings", "router_epsilon", "expert_bias",
            "initializer_range", "conv_initializer_range", "packed_rows",
            "learning_rate"} <= set(cfg["assumed"])
    assert cfg["recipe"]["learning_rate"] == 1e-7
    for key in ("deployment", "cut_to_size", "parameters"):
        assert len(cfg[key]) > 100, key
    assert "four chips" in cfg["deployment"]
    assert cfg["program"] == {"use_recompute": True}


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    real = importlib.util.find_spec

    def absent(name, *a, **kw):
        return None if name.endswith("lfm2_moe") else real(name, *a, **kw)

    monkeypatch.setattr(importlib.util, "find_spec", absent)
    with pytest.raises(SystemExit, match="lfm2_moe"):
        harness.load_module("families", "lfm2")


NEW_READERS = ("short_conv_ms", "gated_conv_ms", "gated_conv_roofline_pct")
TAKEN = ("flash_roofline_pct", "softmax_ce_roofline_pct", "expert_matmul_ms",
         "expert_dispatch_ms", "expert_load_max_over_mean",
         "expert_roofline_pct", "rms_norm_ms")


def test_the_new_cell_is_owed_the_metrics_of_its_rules():
    man = harness.manifest()
    cell = harness.load_cell(CELL, man)
    reported = {m["name"] for m, _ in harness.layer_readers(man, cell)}
    assert set(NEW_READERS) | set(TAKEN) | {
        "attention_ms", "mfu_pct", "step_hbm_gb", "mtp_ms",
        "linear_attention_ms", "window_attention_ms", "state_space_ms",
        "ssm_scan_ms"} <= reported
    assert not reported & {"residual_mix_ms", "collective_mb",
                           "collective_exposed_ms"}
    # every family of the program's own kernels the compiled step holds
    # (megablox's ``gmm`` / ``tgmm`` are jax's names and stay unlisted, as in
    # cells 5-8, so that a kernel of the repo's own may replace them)
    assert cell["kernels"] == ["flash_", "softmax_ce_", "rope_"]
    # no other cell is owed the three new readers: each has a rule
    for other in man["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m, _ in harness.layer_readers(
                man, harness.load_cell(other["name"], man))}
            assert not names & set(NEW_READERS), other
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["traffic"]["eos_token"] == cell["config"]["vocab_size"] - 1
    # four packed rows of 8,192 tokens, each one micro-batch of the
    # reference; a held expert's even share is 4,096 slots
    traffic = cell["traffic"]
    assert (traffic["batch"], traffic["seq_len"]) == (4, 8192)
    assert train.micro_rows(traffic["seq_len"]) == 1
    assert traffic["batch"] * traffic["seq_len"] * \
        cell["config"]["num_experts_per_tok"] // \
        cell["config"]["num_experts_published"] == 4096
    assert "507,820,160" in cell["sizing"]


def test_the_three_rules_read_only_the_cells_own_fields():
    """What ``test_bench_rules.py`` holds the older rules to (its closed set
    cannot name these: ``tests/conftest.py``)."""
    bare = {"chips": 1, "config": {}}
    for name in NEW_READERS:
        reader = harness.load_module("layer_metrics", name)
        assert reader.reports(bare) is False, name
        assert reader.reports(dict(bare, config={"family": "lfm2"})) is False
        assert reader.reports(dict(bare, config={"conv_L_cache": 3})) is True
        # a state-space or delta-rule configuration has a short convolution
        # of another kind and another key: not this reader's
        assert reader.reports(dict(bare, config={"mamba_d_conv": 4})) is False


def test_this_prs_entries_follow_granites_in_order():
    """``BENCHMARK.json`` grows at the end of its lists: PR 48's
    configuration, cell and three readers come after PR 44's last entries
    and in this order. Nothing is said of what follows them: the next PR
    appends there."""
    man = harness.manifest()
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("ssm_scan_roofline_pct")
    assert names[at + 1:at + 4] == list(NEW_READERS)
    configs = [c["name"] for c in man["configs"]]
    assert configs[configs.index("granite-4.0-h-micro") + 1] == CONFIG
    cells = [w["name"] for w in man["workloads"]]
    assert cells[cells.index("granite4h_pretrain_ssm_8k") + 1] == CELL
    layers = dict(zip(NEW_READERS, ("convolution layer", "kernels",
                                    "kernels")))
    for m in man["per_layer"][at + 1:at + 4]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace" and m["layer"] == layers[m["name"]]
    assert man["per_layer"][at + 3]["unit"] == "%"
    # and the cell's name went to the end of the seven lists that take it
    for m in man["per_layer"]:
        listed = CELL in m.get("workloads", ())
        assert listed == (m["name"] in TAKEN + NEW_READERS), m
        if listed and m["name"] in TAKEN:
            before = m["workloads"][m["workloads"].index(CELL) - 1]
            assert before in ("granite4h_pretrain_ssm_8k",
                              "laguna_pretrain_swa_ep32"), m
    entry = man["workloads"][cells.index(CELL)]
    assert entry["traffic"] == "packed_lm_8k_b4_v16384"
    assert "4x" in entry["why"] and "4,096" in entry["why"]
    assert man["configs"][configs.index(CONFIG)]["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5])
def test_the_cells_own_rows_come_from_the_seed_and_the_vocabulary_slice(seed):
    from benchmark import generate

    cell = harness.load_cell(CELL)
    traffic = dict(cell["traffic"], pool_batches=1)
    vocab = cell["config"]["vocab_size"]
    ids, labels = generate.pool(traffic, vocab, seed)
    assert ids.shape == labels.shape == (4, 8192)
    assert 0 <= ids.min() and ids.max() < vocab == 16384
    assert (ids == traffic["eos_token"]).any()    # documents end inside rows
    again, _ = generate.pool(traffic, vocab, seed)
    other, _ = generate.pool(traffic, vocab, seed + 1)
    assert (ids == again).all() and (ids != other).any()


# ---- the costs and the readers -----------------------------------------------
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_conv_costs_against_a_count_by_hand():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert conv_costs.conv_layers(cfg) == 4
    # forward B, C, X in and y out; backward those three and dy in, dB, dC,
    # dX out; 2,048 channels, bfloat16, four layers
    forward, backward = 4 * 2048, (4 + 3) * 2048
    assert conv_costs.gated_conv_bytes_per_position(cfg, 2) == \
        2 * (forward + backward) * 4 == 180_224
    moved = 4 * 8192 * 180_224
    assert 5.9e9 < moved < 6.0e9
    assert conv_costs.gated_conv_roofline_s(cfg, traffic, PEAKS) == \
        pytest.approx(moved / 819e9) == pytest.approx(7.21e-3, rel=2e-3)
    # the FLOPs (about 3 x (2 K + 2) a channel a token) could not bind
    assert 4 * 8192 * 3 * 8 * 2048 * 4 / 197e12 < 0.01 * moved / 819e9
    # a configuration without a convolution layer has nothing to do
    for other in ("granite4h_pretrain_ssm_8k", "gpt2s_pretrain_1k"):
        other = harness.load_cell(other)
        assert conv_costs.conv_layers(other["config"]) == 0
        assert conv_costs.gated_conv_roofline_s(
            other["config"], other["traffic"], PEAKS) == 0.0


def test_the_three_readers_on_a_tiny_table(monkeypatch):
    text = (
        'ENTRY %main (p: f32[8]) -> f32[8] {\n'
        '  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/short_conv/linear_nobias/dot_general"}\n'
        '  %b.2 = f32[8]{0} add(%a.1, %p), metadata={op_name="jit(pure)/'
        'backward/transpose(jvp(recompute))/short_conv/gated_short_conv/'
        'mul"}\n'
        '  %c.3 = f32[8]{0} add(%b.2, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/gqa_attn/sdpa/flash_fwd_causal/pallas_call"}\n'
        '  ROOT %e.5 = f32[8]{0} add(%c.3, %p), metadata={op_name="jit(pure)/'
        'optimizer/mul"}\n}\n')

    def row(instruction, phase, op, kernel, ms):
        return scope_reduce.Row(instruction, phase, op, kernel, ms, 1.0,
                                False)

    table = [row("a.1 fusion", "forward", "linear_nobias", None, 20.0),
             row("b.2 fusion", "backward", "gated_short_conv", None, 36.05),
             row("c.3 fusion", "forward", "sdpa", "flash_fwd_causal", 5.0),
             row("e.5 fusion", "optimizer", None, None, 11.0)]
    cell = harness.load_cell(CELL)
    window = types.SimpleNamespace(compiled_text=text, cell=cell)
    window.scope_table = (table, {})
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS)
    whole, op, share = (harness.load_module("layer_metrics", name)
                        for name in NEW_READERS)
    assert whole.read(window) == 56.05
    assert op.read(window) == 36.05
    # 7.21 ms of bytes at the HBM rate in 36.05 ms: a fifth
    need = 4 * 8192 * 180_224 / 819e9
    assert share.read(window) == pytest.approx(100.0 * need / 36.05e-3)
    assert 19.9 < share.read(window) < 20.1
    assert (whole.LAYER, whole.UNIT) == ("convolution layer", "ms")
    assert (op.LAYER, op.UNIT) == ("kernels", "ms")
    assert (share.LAYER, share.UNIT) == ("kernels", "%")
    for reader in (whole, op, share):
        assert reader.reports(cell) and reader.MOVES == \
            "tokens_per_s_per_chip"
    # a program without the scope and the op (the parent's, were it asked)
    # has nothing to read: the line leaves the metric out, and nothing raises
    window.compiled_text = text.replace("short_conv", "mixer")
    window.scope_table = ([r for r in table
                           if r.program_op != "gated_short_conv"], {})
    assert whole.read(window) is None and op.read(window) is None
    assert share.read(window) is None
    # a program that names no phase has nothing to read either
    window.scope_table = (None, {})
    assert whole.read(window) is None and op.read(window) is None
    assert share.read(window) is None
