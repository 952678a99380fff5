"""The ``granite4h`` family: what the chip configuration counts and states;
at a size a test can hold, the program's first three steps through
``TrainStep`` in bfloat16 (loss, first gradient, parameter change) against
``benchmark/reference/granite4h.py``, the fp8 control failing a limit; the
family's refusal of a program without the model; the cell's own rows from its
seed; ``ssm_costs`` by hand and the three readers on a tiny table; where this
PR's entries stand in the manifest. The model in float32 against the
reference: ``tests/test_ssm_hybrid.py``; the scan alone:
``tests/test_state_space.py``."""
import copy
import importlib.util
import types

import numpy as np
import pytest

import jax.numpy as jnp

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import correct, harness, scope_reduce, ssm_costs
from benchmark.loops import train
from benchmark.reference import granite4h as ref

SEED = 2 ** 31 + 44
CELL = "granite4h_pretrain_ssm_8k"
CONFIG = "granite-4.0-h-micro"

# Readings over four seeds, two of them over 2**31 (CPU, PR 44, at three
# layers; program max / fp8 control min): grad_rel_err 0.0056 / 0.0296,
# grad_norm_gap 0.0086 / 0.0138, delta_norm_gap 0.0029 / 0.0076, loss gaps
# 3e-6 / 2e-6. grad_rel_err's limit lies between its two readings with room
# on both sides (2.3x over the one, 2.3x under the other) and is the number
# the control must fail; the others sit about three times over the program's
# largest (an unchanged state reads delta_norm_gap 1.0, rows left out move
# loss_gap_1 by far more; the losses' limit has seven times of room, since
# bfloat16's rounding of a loss of 6.2 is itself 2e-6 of it).
LIMITS = {"loss_gap_1": 2e-5, "loss_gap_2": 2e-5, "loss_gap_3": 2e-5,
          "grad_norm_gap": 0.026, "grad_rel_err": 0.013,
          "delta_norm_gap": 0.009}


def tiny_config(**kw):
    """Three layers (mamba, attention, mamba), 4 state-space heads of 32 over
    a state of 8 in chunks of 16, 4 query heads over 2 key/value heads."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    cfg.update(hidden_size=64, intermediate_size=96,
               shared_intermediate_size=96, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
               mamba_d_head=32, mamba_d_state=8, mamba_chunk_size=16,
               vocab_size=512)
    cfg.update(kw)
    return cfg


def tiny_cell():
    traffic = harness.load_json("traffic", "packed_lm_8k_b1_v12544.json")
    traffic.update(batch=4, seq_len=40, pool_batches=4, eos_token=511)
    traffic["documents"]["median_len"] = 20
    return {"name": "tiny_granite4h", "chips": 1, "loop": "train",
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


def test_every_parameter_is_compared_and_the_gauges_ran(readings):
    got, want, _, su, cell = readings
    cfg = cell["config"]
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == \
        {name for name, _, _ in ref.param_specs(cfg)}
    assert "head" not in got["grad_norms"]          # one tied leaf
    assert all(np.isfinite(v) and v > 0 for v in want["grad_norms"].values())
    # one signature for all three steps: no buffer changed its type on the way
    assert len(su.step._compiled) == 1
    stats = su.model.state_space_stats._data
    assert stats.dtype == jnp.bfloat16
    low, dt_mean = (float(x) for x in stats)
    # ln 2 a token at the seeded zeros, 16 tokens a chunk
    assert -16 < low < -6 and 0.6 < dt_mean < 0.8
    # both kinds of sublayer name their work, and the scan is a program op
    text = su.step.compiled().as_text()
    for scope in ("state_space", "gqa_attn"):
        assert f"jvp({scope})" in text or f"/{scope}/" in text, scope
    assert "ssm_chunk" in text and "ssm_gate" in text


# ---- the chip configuration --------------------------------------------------
def test_the_chip_configuration_counts_as_its_file_says():
    cfg = harness.load_json("configs", CONFIG + ".json")
    family = harness.load_module("families", "granite4h")
    specs = family.reference.param_specs(cfg)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert total == 772_160_448 and f"{total:,}" in cfg["parameters"]
    sizes = {n: int(np.prod(s)) for n, s, _ in specs}

    def under(prefix):
        return sum(v for n, v in sizes.items() if n.startswith(prefix))

    # the issue's table, row by row
    assert sizes["layers.0.mixer.in_proj"] == 2048 * (4096 + 4352 + 64) == \
        17_432_576
    assert sizes["layers.0.mixer.conv"] + sizes["layers.0.mixer.conv_bias"] \
        == 4352 * 4 + 4352
    assert sizes["layers.0.mixer.dt_bias"] + sizes["layers.0.mixer.A_log"] + \
        sizes["layers.0.mixer.D"] == 3 * 64
    assert sizes["layers.0.mixer.norm"] == 4096
    assert sizes["layers.0.mixer.out_proj"] == 8_388_608
    assert under("layers.0.mixer.") == 25_847_232
    assert under("layers.0.mlp.") == 3 * 2048 * 8192 == 50_331_648
    assert under("layers.5.attn.") == 2 * 2048 * 2048 + 2 * 2048 * 512 == \
        10_485_760
    assert under("layers.0.") == 76_182_976
    assert under("layers.5.") == 60_821_504
    assert under("layers.") == 9 * 76_182_976 + 60_821_504 == 746_468_288
    assert sizes["embed"] + sizes["norm"] == 12544 * 2048 + 2048 == 25_692_160
    assert "head" not in sizes
    for number in (25_847_232, 10_485_760, 76_182_976, 60_821_504,
                   746_468_288, 50_331_648):
        assert f"{number:,}" in cfg["parameters"], number
    assert set(family.name_map(cfg).values()) == set(sizes)
    pcfg = family.program_config(cfg)
    assert pcfg.layer_types == ("mamba",) * 5 + ("attention",) + \
        ("mamba",) * 4
    assert (pcfg.ssm_heads, pcfg.ssm_head_dim, pcfg.ssm_state, pcfg.conv_size,
            pcfg.chunk) == (64, 64, 128, 4, 256)
    assert (pcfg.heads, pcfg.kv_heads, pcfg.head_dim, pcfg.mlp_width) == \
        (32, 8, 64, 8192)
    assert (pcfg.embedding_multiplier, pcfg.residual_multiplier,
            pcfg.attention_multiplier, pcfg.logits_scaling) == \
        (12, 0.22, 0.015625, 8)
    assert pcfg.attention_multiplier != pcfg.head_dim ** -0.5   # 1/64, not 1/8
    assert pcfg.use_recompute and pcfg.rms_eps == 1e-5
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["vocab_size"] % 128 == 0
    assert "n_routed_experts" not in cfg     # the expert readers are not owed
    cell = harness.load_cell(CELL)
    length = cell["traffic"]["seq_len"]
    # every leaf once (the tied matrix as the head), 32 heads of 64 + 64 over
    # the whole row, 5 P N a token a head in nine layers, all three times
    assert family.step_flops(cfg, cell["traffic"]) == length * (
        6.0 * total + 6.0 * 32 * 128 * length +
        3.0 * 9 * 64 * 5 * 64 * 128)
    # a program that cannot be this model is refused, not approximated
    with pytest.raises(ValueError, match="tied"):
        family.program_config(dict(cfg, tie_word_embeddings=False))
    with pytest.raises(ValueError, match="one"):
        ref.mamba_sizes(dict(cfg, mamba_n_groups=8))


def test_every_published_number_stands_unless_reduced_names_it():
    """The catalog's row (``model-configs`` guide, granite-4.0-h-micro) as it
    was read for PR 44: every key under its own name, changed only where
    ``reduced`` says so; the list a layer is cut to its first ten entries."""
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192, "layer_types": period * 4,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert [i for i, t in enumerate(published["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    cfg = harness.load_json("configs", CONFIG + ".json")
    entry = [c for c in harness.manifest()["configs"]
             if c["name"] == CONFIG][0]
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"]) == \
        set(cfg["changed"]) == {"num_hidden_layers", "layer_types",
                                "vocab_size"}
    assert cfg["layer_types"] == published["layer_types"][:10]
    for key in ("num_hidden_layers", "vocab_size"):
        assert cfg[key + "_published"] == published[key], key
    assert entry["source"] == cfg["source"]
    # what the source does not give is said, each with its reason
    assert {"initializer_range", "A_log", "dt_bias", "conv_initializer_range",
            "packed_rows", "learning_rate"} <= set(cfg["assumed"])
    assert cfg["recipe"]["learning_rate"] == 1e-5
    for key in ("deployment", "cut_to_size", "parameters"):
        assert len(cfg[key]) > 100, key
    assert cfg["program"] == {"use_recompute": True}


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    real = importlib.util.find_spec

    def absent(name, *a, **kw):
        return None if name.endswith("ssm_hybrid") else real(name, *a, **kw)

    monkeypatch.setattr(importlib.util, "find_spec", absent)
    with pytest.raises(SystemExit, match="ssm_hybrid"):
        harness.load_module("families", "granite4h")


def test_the_new_cell_is_owed_the_metrics_of_its_rules():
    man = harness.manifest()
    cell = harness.load_cell(CELL, man)
    reported = {m["name"] for m, _ in harness.layer_readers(man, cell)}
    assert {"state_space_ms", "ssm_scan_ms", "ssm_scan_roofline_pct",
            "attention_ms", "mtp_ms", "linear_attention_ms",
            "window_attention_ms", "rms_norm_ms", "mfu_pct",
            "flash_roofline_pct", "softmax_ce_roofline_pct"} <= reported
    assert not reported & {"residual_mix_ms", "collective_mb",
                           "collective_exposed_ms", "expert_matmul_ms",
                           "expert_dispatch_ms", "expert_load_max_over_mean",
                           "expert_roofline_pct"}
    assert cell["kernels"] == ["flash_", "softmax_ce_"]
    # every other training cell is owed the three new readers too (they
    # read 0)
    for other in man["workloads"]:
        names = {m["name"] for m, _ in harness.layer_readers(
            man, harness.load_cell(other["name"], man))}
        assert {"state_space_ms", "ssm_scan_ms",
                "ssm_scan_roofline_pct"} <= names, other
    assert cell["chips"] == 1 and cell["mesh"] is None
    assert cell["traffic"]["eos_token"] == cell["config"]["vocab_size"] - 1
    # one packed row of 8,192 tokens is a micro-batch of the reference, whole
    # chunks of the scan and whole segments of the reference's recurrence
    assert cell["traffic"]["seq_len"] == 8192 == \
        32 * cell["config"]["mamba_chunk_size"] == 128 * ref.SEGMENT
    assert train.micro_rows(cell["traffic"]["seq_len"]) == 1


def test_this_prs_entries_follow_lagunas_in_order():
    """``BENCHMARK.json`` grows at the end of its lists: PR 44's
    configuration, cell and three readers come after PR 42's last entries
    and in this order (``tests/conftest.py`` says why
    ``test_bench_laguna.py``'s own assertion sees the lists up to those).
    Nothing is said of what follows them: the next PR appends there."""
    man = harness.manifest()
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("swa_roofline_pct")
    assert names[at - 1] == "window_attention_ms"
    assert names[at + 1:at + 4] == ["state_space_ms", "ssm_scan_ms",
                                    "ssm_scan_roofline_pct"]
    configs = [c["name"] for c in man["configs"]]
    assert configs[configs.index("laguna-s-2.1") + 1] == CONFIG
    cells = [w["name"] for w in man["workloads"]]
    assert cells[cells.index("laguna_pretrain_swa_ep32") + 1] == CELL
    for m in man["per_layer"][at + 1:at + 4]:
        assert "workloads" not in m and m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # and the cell's name went to the end of the three lists that take it
    for m in man["per_layer"]:
        listed = CELL in m.get("workloads", ())
        assert listed == (m["name"] in (
            "rms_norm_ms", "flash_roofline_pct", "softmax_ce_roofline_pct")), m
        if listed:
            assert m["workloads"].index(CELL) == \
                m["workloads"].index("laguna_pretrain_swa_ep32") + 1


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


# ---- the costs and the readers -----------------------------------------------
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_ssm_costs_against_a_count_by_hand():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert ssm_costs.mamba_layers(cfg) == 9
    # a token a head: decay 64 x 128, outer product and read 2 x 64 x 128
    # each; 64 heads, nine layers, three passes
    assert ssm_costs.scan_flops_per_position(cfg) == \
        3 * (8192 + 2 * 8192 + 2 * 8192) * 64 * 9 == 70_778_880
    # forward x, Delta, B, C in and y out; backward those and dy in, dx,
    # dDelta, dB, dC out; bfloat16
    forward = 4096 + 64 + 128 + 128 + 4096
    backward = (4096 + 64 + 128 + 128 + 4096) + (4096 + 64 + 128 + 128)
    assert ssm_costs.scan_bytes_per_position(cfg, 2) == \
        2 * (forward + backward) * 9 == 385_920
    flops, moved = 8192 * 70_778_880, 8192 * 385_920
    assert 0.57e12 < flops < 0.59e12 and 3.1e9 < moved < 3.2e9
    # the bytes bind: 3.86 ms against 2.94
    assert ssm_costs.scan_roofline_s(cfg, traffic, PEAKS) == \
        pytest.approx(moved / 819e9) == pytest.approx(3.86e-3, rel=2e-3)
    assert flops / 197e12 < moved / 819e9
    # a configuration without a state-space layer has nothing to do
    other = harness.load_cell("laguna_pretrain_swa_ep32")
    assert ssm_costs.mamba_layers(other["config"]) == 0
    assert ssm_costs.scan_roofline_s(other["config"], other["traffic"],
                                     PEAKS) == 0.0
    gpt = harness.load_cell("gpt2s_pretrain_1k")
    assert ssm_costs.scan_roofline_s(gpt["config"], gpt["traffic"],
                                     PEAKS) == 0.0


def test_the_three_readers_on_a_tiny_table(monkeypatch):
    text = (
        'ENTRY %main (p: f32[8]) -> f32[8] {\n'
        '  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/state_space/linear_nobias/dot_general"}\n'
        '  %b.2 = f32[8]{0} add(%a.1, %p), metadata={op_name="jit(pure)/'
        'backward/transpose(jvp(recompute))/state_space/ssm_chunk/while"}\n'
        '  %c.3 = f32[8]{0} add(%b.2, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/gqa_attn/sdpa/flash_fwd_causal/pallas_call"}\n'
        '  ROOT %e.5 = f32[8]{0} add(%c.3, %p), metadata={op_name="jit(pure)/'
        'optimizer/mul"}\n}\n')

    def row(instruction, phase, op, kernel, ms):
        return scope_reduce.Row(instruction, phase, op, kernel, ms, 1.0,
                                False)

    table = [row("a.1 fusion", "forward", "linear_nobias", None, 2.0),
             row("b.2", "backward", "ssm_chunk", None, 38.6),
             row("c.3 fusion", "forward", "sdpa", "flash_fwd_causal", 5.0),
             row("e.5 fusion", "optimizer", None, None, 11.0)]
    cell = harness.load_cell(CELL)
    window = types.SimpleNamespace(compiled_text=text, cell=cell)
    window.scope_table = (table, {})
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS)
    whole = harness.load_module("layer_metrics", "state_space_ms")
    scan = harness.load_module("layer_metrics", "ssm_scan_ms")
    share = harness.load_module("layer_metrics", "ssm_scan_roofline_pct")
    assert whole.read(window) == 40.6
    assert scan.read(window) == 38.6
    # 3.86 ms of bytes at the HBM rate in 38.6 ms: a tenth
    need = 8192 * 385_920 / 819e9
    assert share.read(window) == pytest.approx(100.0 * need / 38.6e-3)
    assert 9.9 < share.read(window) < 10.1
    assert (whole.LAYER, whole.UNIT) == ("state-space layer", "ms")
    assert (scan.LAYER, scan.UNIT) == ("kernels", "ms")
    assert (share.LAYER, share.UNIT) == ("kernels", "%")
    for reader in (whole, scan, share):
        assert not hasattr(reader, "reports")
        assert reader.MOVES == "tokens_per_s_per_chip"
    # a step with no state-space layer reads 0, not nothing: all three are
    # owed in every training cell, the eight that were there too
    window.compiled_text = text.replace("state_space", "mtp")
    window.scope_table = ([r for r in table if r.program_op != "ssm_chunk"],
                          {})
    assert whole.read(window) == 0.0 and scan.read(window) == 0.0
    assert share.read(window) == 0.0
    window.cell = harness.load_cell("laguna_pretrain_swa_ep32")
    assert share.read(window) == 0.0
    # a program that names no phase has nothing to read
    window.scope_table = (None, {})
    assert whole.read(window) is None and scan.read(window) is None
    assert share.read(window) is None
