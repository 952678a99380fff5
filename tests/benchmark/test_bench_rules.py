"""Which cells a per-layer reader reports in, what ``correct`` asks of a
compiled step's kernels, and what a family counts for a step: each is a rule
on what is read, stated in the cell's own fields, never a stand-in for a
family. Synthetic cells here have what an old stand-in looked for and not
what the reader reads, and the reverse."""
import gzip
import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness

MAN = harness.manifest()
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "gpt2s_step_scopes.json.gz")
EXPERT_METRICS = ("expert_matmul_ms", "expert_dispatch_ms",
                  "expert_load_max_over_mean", "expert_roofline_pct")

# synthetic cells: only the fields a rule may look at
ROUTED_PLAIN_RESIDUAL = {            # the next configuration's kind
    "chips": 1, "kernels": ["flash_"],
    "config": {"n_routed_experts": 8, "rms_norm_eps": 1e-6}}
STREAMS_NO_EXPERTS = {
    "chips": 1, "config": {"hc_mult": 4, "layer_norm_eps": 1e-5}}
DENSE_RMS_DECODER = {"chips": 1, "config": {"rms_norm_eps": 1e-5}}
ONE_STREAM_NAMED = {"chips": 1, "config": {"hc_mult": 1, "n_layer": 12}}
OLD_COUNT_ONLY = {"chips": 1, "min_pallas_calls": 1, "config": {}}
CE_KERNEL_ONLY = {"chips": 1, "kernels": ["softmax_ce_", "layer_norm_"],
                  "config": {}}
FOUR_CHIPS = {"chips": 4, "config": {}}

OWED = [
    ("routed_plain_residual", ROUTED_PLAIN_RESIDUAL,
     EXPERT_METRICS + ("rms_norm_ms", "flash_roofline_pct"),
     ("residual_mix_ms", "softmax_ce_roofline_pct", "collective_mb",
      "collective_exposed_ms")),
    ("streams_no_experts", STREAMS_NO_EXPERTS, ("residual_mix_ms",),
     EXPERT_METRICS + ("rms_norm_ms", "flash_roofline_pct",
                       "softmax_ce_roofline_pct")),
    ("dense_rms_decoder", DENSE_RMS_DECODER, ("rms_norm_ms",),
     EXPERT_METRICS + ("residual_mix_ms",)),
    ("one_stream_named", ONE_STREAM_NAMED, (),
     ("residual_mix_ms", "rms_norm_ms")),
    ("old_count_only", OLD_COUNT_ONLY, (),
     ("flash_roofline_pct", "softmax_ce_roofline_pct")),
    ("ce_kernel_only", CE_KERNEL_ONLY, ("softmax_ce_roofline_pct",),
     ("flash_roofline_pct",)),
    ("four_chips", FOUR_CHIPS, ("collective_mb", "collective_exposed_ms"),
     EXPERT_METRICS + ("residual_mix_ms", "rms_norm_ms")),
]
CASES = [pytest.param(cell, metric, owed, id=f"{name}-{metric}")
         for name, cell, taken, left in OWED
         for metric, owed in [(m, True) for m in taken] +
         [(m, False) for m in left]]


@pytest.mark.parametrize("cell,metric,owed", CASES)
def test_a_cell_is_owed_a_metric_by_what_its_reader_reads(cell, metric, owed):
    cell = dict(cell, name="synthetic")
    reported = {m["name"] for m, _ in harness.layer_readers(MAN, cell)}
    assert (metric in reported) == owed
    # a metric with no rule of its own is owed in every training cell
    assert {"mfu_pct", "attention_ms", "pallas_calls"} <= reported


def test_every_reports_rule_reads_only_the_cells_own_fields():
    """A rule is asked with nothing but the cell: it may not build a model,
    read a window or ask a family's name."""
    ruled = [m["name"] for m in MAN["per_layer"] if hasattr(
        harness.load_module("layer_metrics", m["name"]), "reports")]
    assert set(ruled) == set(EXPERT_METRICS) | {
        "residual_mix_ms", "rms_norm_ms", "flash_roofline_pct",
        "softmax_ce_roofline_pct", "collective_mb", "collective_exposed_ms"}
    bare = {"chips": 1, "config": {}}
    for name in ruled:
        reader = harness.load_module("layer_metrics", name)
        assert reader.reports(bare) is False, name
        assert reader.reports(dict(bare, config={"family": "xing4"})) is False


# ---- the kernels a compiled step must hold -------------------------------------
@pytest.fixture(scope="module")
def step_text():
    """Cell gpt2s_pretrain_1k's entry computation as the chip compiled it
    (PR 24's recorded fixture): 88 Mosaic calls of seven kernels."""
    with gzip.open(FIXTURE, "rt") as f:
        return "\n".join(json.load(f)["entry"])


def test_the_compiled_step_names_its_kernels(step_text):
    assert harness.mosaic_calls(step_text) == 88
    assert harness.mosaic_kernels(step_text) == {
        "flash_fwd_causal", "flash_bwd_dq_causal", "flash_bwd_dkv_causal",
        "layer_norm_fwd", "layer_norm_bwd", "softmax_ce_fwd", "softmax_ce_bwd"}
    listed = harness.load_json("workloads", "gpt2s_pretrain_1k.json")["kernels"]
    assert listed == ["flash_", "softmax_ce_", "layer_norm_"]
    assert harness.missing_kernels(step_text, listed) == []


def test_a_step_whose_flash_calls_went_dense_misses_one_kernel(step_text):
    """The old check, at least one Mosaic call of any name, read 0 missing on
    a step that kept the CE and layer-norm kernels and lost attention's."""
    dense = "\n".join(line for line in step_text.splitlines()
                      if not ("tpu_custom_call" in line and "%flash_" in line))
    assert max(0, 1 - harness.mosaic_calls(dense)) == 0   # the old number
    assert harness.mosaic_kernels(dense) == {
        "layer_norm_fwd", "layer_norm_bwd", "softmax_ce_fwd", "softmax_ce_bwd"}
    assert harness.missing_kernels(
        dense, ["flash_", "softmax_ce_", "layer_norm_"]) == ["flash_"]
    assert harness.missing_kernels(dense, ["layer_norm_"]) == []
    assert harness.missing_kernels("", ["flash_", "layer_norm_"]) == \
        ["flash_", "layer_norm_"]


def test_a_name_that_only_holds_the_prefix_does_not_count():
    line = ('  %fusion.flash_1 = bf16[8]{0} fusion(%p), kind=kLoop\n'
            '  %my_flash_fwd.3 = bf16[8]{0} custom-call(%p), '
            'custom_call_target="tpu_custom_call"\n'
            '  ROOT %gmm.12 = bf16[8]{0} custom-call(%p), '
            'custom_call_target="tpu_custom_call"\n')
    assert harness.mosaic_kernels(line) == {"my_flash_fwd", "gmm"}
    assert harness.missing_kernels(line, ["flash_", "gmm"]) == ["flash_"]


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_a_roofline_reader_reports_where_its_kernels_are_listed(cell):
    loaded = harness.load_cell(cell, MAN)
    reported = {m["name"] for m, _ in harness.layer_readers(MAN, loaded)}
    for metric, prefix in (("flash_roofline_pct", "flash_"),
                           ("softmax_ce_roofline_pct", "softmax_ce_")):
        assert (metric in reported) == (prefix in loaded["kernels"])
    assert "min_pallas_calls" not in loaded


# ---- what a family counts for a step -------------------------------------------
@pytest.mark.parametrize("cell,per_position", [
    ("gpt2s_pretrain_1k", 860_101_632),
    ("gpt2s_pretrain_1k_dp4", 860_101_632),
    ("bert_base_mlm_512", 717_261_672),
    ("bert_base_mlm_128", None),
    ("xing4_pretrain_ep8", None)])
def test_a_step_counts_batch_x_length_x_flops_per_position(cell, per_position):
    loaded = harness.load_cell(cell, MAN)
    cfg, traffic = loaded["config"], loaded["traffic"]
    family = harness.load_module("families", cfg["family"])
    per = family.flops_per_position(cfg, traffic["seq_len"])
    if per_position is not None:       # test_bench_pure.py's by-hand counts
        assert per == per_position
    by_hand = traffic["batch"] * traffic["seq_len"] * per
    assert family.step_flops(cfg, traffic) == by_hand
    assert by_hand == float(int(by_hand))    # exact in a double
    # the family is asked with the mix, so a second batch is a second count
    assert family.step_flops(cfg, dict(traffic, batch=2 * traffic["batch"])) \
        == 2 * by_hand


def test_mfu_reads_the_familys_count_of_the_cells_mix(monkeypatch):
    import types

    import jax

    asked = []

    def step_flops(cfg, traffic):
        asked.append((cfg, traffic))
        return 2.0e12

    cell = {"chips": 2, "config": {"family": "any"},
            "traffic": {"batch": 4, "seq_len": 8, "patches": 100}}
    window = types.SimpleNamespace(
        cell=cell, steps=10, seconds=5.0,
        family=types.SimpleNamespace(step_flops=step_flops))
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1.0e13})
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="any")])
    reader = harness.load_module("layer_metrics", "mfu_pct")
    # 10 steps x 2e12 FLOPs in 5 s over 2 chips x 1e13 FLOP/s
    assert reader.read(window) == pytest.approx(20.0)
    assert asked == [(cell["config"], cell["traffic"])]
