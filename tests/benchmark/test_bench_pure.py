"""The benchmark's pure parts: traffic from a seed, the FLOPs functions
against hand-worked values, and the reduction from trace to numbers."""
import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import generate, harness, trace_reduce as tr

masked_lm = harness.load_module("traffic", "masked_lm")

MAN = harness.manifest()
BIG = 2 ** 31 + 12345   # more than 32 signed bits hold
TRAFFIC = sorted({w["traffic"] for w in MAN["workloads"]})
VOCAB = {w["traffic"]: harness.load_cell(w["name"], MAN)["config"]["vocab_size"]
         for w in MAN["workloads"]}


# ---- traffic ---------------------------------------------------------------
@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_repeats_for_a_seed_and_differs_across_seeds(name):
    params = harness.load_json("traffic", name + ".json")
    a = generate.pool(params, VOCAB[name], BIG)
    b = generate.pool(params, VOCAB[name], BIG)
    c = generate.pool(params, VOCAB[name], BIG + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    rows = params["pool_batches"] * params["batch"]
    assert all(len(x) == rows and x.dtype == np.int32 for x in a)
    assert a[0].shape == (rows, params["seq_len"])
    assert a[0].min() >= 0 and a[0].max() < VOCAB[name]


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_seed_gets_the_same_amount_of_work(name):
    params = harness.load_json("traffic", name + ".json")
    family = harness.load_module(
        "families", {"causal_lm": "gpt2", "masked_lm": "bert"}[
            params["objective"]])
    counts = [sorted(family.valid_tokens(generate.pool(params, VOCAB[name], s)))
              for s in (1, 2, BIG)]
    assert counts[0] == counts[1] == counts[2]


def test_causal_rows_are_packed_full_and_labels_are_the_ids_shifted():
    params = harness.load_json("traffic", "packed_lm_1k_b16.json")
    ids, labels = generate.pool(params, 50304, 3)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert (ids == params["eos_token"]).sum() > len(ids)  # documents end inside rows


@pytest.mark.parametrize("name", ["bert_mlm_512_b24", "bert_mlm_128_b128"])
def test_masked_lm_rows_follow_berts_recipe(name):
    p = harness.load_json("traffic", name + ".json")
    ids, types, mask, labels, nsp = generate.pool(p, 30522, 5)
    rows, length = ids.shape
    lengths = mask.sum(axis=1)
    assert np.array_equal(np.sort(lengths), masked_lm.lengths_of(p, rows))
    assert abs((lengths < length).mean() - p["short_seq_prob"]) < 0.02
    assert lengths.min() >= masked_lm.MIN_PAIR
    sp = p["special"]
    for r in (0, rows // 2, rows - 1):
        n = lengths[r]
        assert ids[r, 0] == sp["cls"] and ids[r, n - 1] == sp["sep"]
        assert (ids[r, n:] == sp["pad"]).all() and (mask[r, :n] == 1).all()
        first_sep = int(np.argmax(ids[r] == sp["sep"]))
        assert (types[r, :first_sep + 1] == 0).all()
        assert (types[r, first_sep + 1:n] == 1).all()
        assert (labels[r, n:] == generate.IGNORE).all()
    predicted = (labels != generate.IGNORE).sum(axis=1)
    assert predicted.max() <= p["max_predictions_per_seq"]
    full = lengths == length
    assert (predicted[full] == min(p["max_predictions_per_seq"],
                                   round(length * p["masked_lm_prob"]))).all()
    shown = ids[labels != generate.IGNORE]
    assert 0.7 < (shown == sp["mask"]).mean() < 0.9
    assert 0.3 < nsp.mean() < 0.7


def test_an_objective_is_a_file_found_by_its_name(tmp_path, monkeypatch):
    """A mix names its objective and ``traffic/<objective>.py`` makes the
    rows: a new objective edits no file that is there."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "ones.py").write_text(
        "import numpy as np\n"
        "def rows(p, vocab_size, rng, n):\n"
        "    return (np.ones((n, p['seq_len']), np.int32),)\n")
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    (ids,) = generate.pool({"objective": "ones", "batch": 2, "seq_len": 3,
                            "pool_batches": 4}, 10, BIG)
    assert ids.shape == (8, 3) and ids.all()


# ---- the step period -------------------------------------------------------
def test_step_period_p90_by_hand():
    from benchmark.loops import train

    gaps = [0.1] * 18 + [0.2, 0.1]          # one slow step of twenty
    stamps = list(np.cumsum([0.0] + gaps))
    assert train.period_p90(stamps) == pytest.approx(100.0)
    slow = [0.1] * 17 + [0.2] * 3            # three: the tail sees them
    assert train.period_p90(list(np.cumsum([0.0] + slow))) == \
        pytest.approx(200.0)
    # spans of 250 ms or more: three steps of 100 ms. Two of the eighteen
    # spans hold the slow step (133.3 ms a step); the 90th percentile lies
    # 0.3 of the way from the sixteenth value (100) to the seventeenth
    assert train.smooth_steps(stamps) == 3
    assert train.period_p90(stamps, 3) == pytest.approx(110.0)
    assert train.smooth_steps([0.0, 0.3, 0.6]) == 1
    window = harness.Window(
        cell={}, family=None, compiled=None, compiled_text="", spans=[],
        stamps=stamps, steps=20, seconds=2.1, first_step_s=0.0,
        compiles_in_window=0)
    reader = harness.load_module("layer_metrics", "step_ms_p90_smooth")
    assert reader.read(window) == pytest.approx(110.0)


def test_collections_in_the_window_are_host_spans():
    import gc

    from benchmark.loops import train

    spans = []
    watch = train.GcSpans(spans)
    gc.callbacks.append(watch)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(watch)
    assert [n for n, _, _ in spans] == ["gc2"]
    assert spans[0][2] >= spans[0][1]


# ---- FLOPs -----------------------------------------------------------------
def test_gpt2_small_flops_per_position_by_hand():
    cfg = harness.load_json("configs", "gpt2-small.json")
    fam = harness.load_module("families", "gpt2")
    block = 2 * 2 * 768 + (768 * 2304 + 2304) + (768 * 768 + 768) + \
        (768 * 3072 + 3072) + (3072 * 768 + 768)
    n = 50304 * 768 + 1024 * 768 + 12 * block + 2 * 768
    assert n == 124_475_904
    specs = fam.reference.param_specs(cfg)
    assert sum(int(np.prod(s)) for _, s, _ in specs) == n
    assert fam.flops_per_position(cfg, 1024) == 6 * n + 12 * 12 * 768 * 1024
    assert fam.flops_per_position(cfg, 1024) == 860_101_632


def test_bert_base_flops_per_position_by_hand():
    cfg = harness.load_json("configs", "bert-base-uncased.json")
    fam = harness.load_module("families", "bert")
    layer = 4 * (768 * 768 + 768) + 2 * 768 + (768 * 3072 + 3072) + \
        (3072 * 768 + 768) + 2 * 768
    n = 30522 * 768 + 512 * 768 + 2 * 768 + 2 * 768 + 12 * layer + \
        2 * (768 * 768 + 768) + 2 * 768 + 30522 + (768 * 2 + 2)
    assert n == 110_106_428
    specs = fam.reference.param_specs(cfg)
    assert sum(int(np.prod(s)) for _, s, _ in specs) == n
    assert fam.flops_per_position(cfg, 512) == 717_261_672


@pytest.mark.parametrize("family,config", [("gpt2", "gpt2-small"),
                                           ("bert", "bert-base-uncased")])
def test_name_map_covers_the_reference_leaves_once(family, config):
    cfg = harness.load_json("configs", config + ".json")
    fam = harness.load_module("families", family)
    names = fam.name_map(cfg)
    leaves = [n for n, _, _ in fam.reference.param_specs(cfg)]
    assert sorted(names.values()) == sorted(leaves)


def test_peaks_table_names_its_source_and_refuses_an_unknown_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in harness.load_json("peaks.json")
    with pytest.raises(SystemExit):
        harness.peaks("cpu")


# ---- trace reduction: a synthetic two-line case ----------------------------
def test_union_subtract_and_gaps():
    assert tr.union([(5, 7), (0, 3), (2, 4), (7, 8)]) == [(0, 4), (5, 8)]
    assert tr.total(tr.union([(0, 3), (2, 4)])) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_exposed_collective_time_on_two_lines():
    # compute 0-10 and 14-20; an all-reduce 8-14 is hidden for 2 and exposed
    # for 4; its async pair start/done 20-21 / 24-26 is exposed throughout
    events = [("fusion.1 fusion", 0, 10), ("all-reduce.3 all-reduce", 8, 14),
              ("fusion.2 fusion", 14, 20),
              ("all-reduce-start.1 all-reduce-start", 20, 21),
              ("fusion.9 fusion", 21, 24), ("ar.1 all-reduce-done", 24, 26)]
    assert tr.exposed_collective_ns(events) == 4 + 1 + 2
    assert tr.is_collective("all-gather.7 all-gather")
    assert tr.is_collective("all-to-all") and tr.is_collective("x all-to-all")
    assert not tr.is_collective("fusion.all-reduce fusion")


def test_label_reads_instruction_and_opcode_from_the_traces_names():
    assert tr.label("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8] "
                    "%p.1), kind=kLoop, calls=%fused_computation.3") == \
        "fusion.3 fusion"
    assert tr.label("%copy-start = (u32[2]{0:T(128)S(1)}, u32[2]{0}, u32[]{:S(2)}) "
                    "copy-start(u32[2]{0:T(128)} %key.1)") == \
        "copy-start copy-start"
    mosaic = tr.label(
        "%transpose_jvp___.86 = (bf16[192,1024,64]{2,1,0}, bf16[192,1024,64]"
        "{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %bitcast.1655), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert mosaic == "transpose_jvp___.86 custom-call:tpu_custom_call"
    assert tr.is_mosaic(mosaic) and not tr.is_mosaic("fusion.3 fusion")
    assert tr.label("bench.block") == "bench.block"
    assert tr.is_collective(tr.label(
        "%all-reduce.5 = f32[768]{0} all-reduce(f32[768]{0} %x), channel_id=1"))


def test_busy_idle_share_and_labels():
    ops = {"/device:TPU:0": [("a", 0, 40), ("b", 50, 100)],
           "/device:TPU:1": [("a", 0, 30), ("b", 50, 100)]}
    busy_s, window_s, idle_pct = tr.busy_and_idle(ops)
    assert busy_s == pytest.approx(85e-9) and window_s == pytest.approx(100e-9)
    assert idle_pct == pytest.approx(20.0)    # the worst device
    assert tr.share_of(ops["/device:TPU:0"], lambda n: n == "b") == pytest.approx(100 * 50 / 90)
    assert tr.time_by_name([("x", 0, 2), ("y", 2, 3), ("x", 5, 9)]) == \
        [("x", 6e-9), ("y", 1e-9)]
    host = [("dispatch", 35, 44), ("block", 44, 60)]
    assert tr.label_gaps([(40, 50)], host) == [("block", 10e-9)]
    assert tr.label_gaps([(200, 210)], host) == [("outside", 10e-9)]


# ---- trace reduction: the recorded fixture ---------------------------------
@pytest.fixture(scope="module")
def recorded():
    import gzip

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "gpt2s_step_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_fixture_one_step_of_gpt2_small_on_the_chip(recorded):
    ops = [tuple(e) for e in recorded["ops"]]
    period = recorded["period_ns"]
    assert len(ops) == 4590 and period == 221_724_043
    busy = tr.union(tr.spans_of(ops))
    # the TensorCore runs one op at a time: the union is the sum
    assert tr.total(busy) == sum(e - s for _, s, e in ops) == 221_337_818
    idle = tr.gaps(busy, 0, period)
    assert tr.total(idle) + tr.total(busy) == period
    busy_s, window_s, idle_pct = tr.busy_and_idle({"/device:TPU:0": ops})
    assert busy_s == pytest.approx(0.221337818)
    assert 0 < idle_pct < 0.2 and window_s <= period / 1e9
    # 88 Mosaic calls a step, 60.75% of the busy time; no collective on one chip
    assert sum(tr.is_mosaic(n) for n, _, _ in ops) == 88
    assert tr.share_of(ops, tr.is_mosaic) == pytest.approx(60.7514, abs=1e-3)
    assert tr.exposed_collective_ns(ops) == 0
    name, seconds = tr.time_by_name(ops)[0]
    assert tr.is_mosaic(name) and seconds == pytest.approx(0.015139145)
    host = [(n[len("bench."):], s, e) for n, s, e in recorded["host"]]
    labels = dict(tr.label_gaps(idle, host))
    assert max(labels, key=labels.get) == "block"


def test_fixture_raw_names_reduce_to_their_opcodes(recorded):
    for opcode, raw in recorded["raw_names"].items():
        assert tr.label(raw).split(" ")[1] == opcode
        assert tr.is_mosaic(tr.label(raw)) == (opcode == tr.MOSAIC)


# ---- counts from the HLO ---------------------------------------------------
def test_collective_bytes_counts_tuple_results_by_hand():
    from benchmark import hlo_count

    hlo = """
  %all-reduce.101 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%a, %b), channel_id=2
  %all-reduce.103 = (bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,3072]{1,0:T(8,128)(2,1)}, /*index=2*/bf16[768]{0:T(1024)(128)(2,1)}) all-reduce(%c, %d, %e), channel_id=3
  %all-reduce.2 = bf16[50304,768]{1,0:T(8,128)(2,1)} all-reduce(%conv), channel_id=4
  %ag = (f32[4,8]{1,0}, f32[16,8]{1,0}) all-gather-start(%x), dimensions={0}
  %agd = f32[16,8]{1,0} all-gather-done(%ag)
  %fusion.all-reduce = f32[8]{0} fusion(%y), kind=kLoop
"""
    got = hlo_count.collective_bytes(hlo)
    assert got == {
        "all-reduce": 8 + 2 * (768 * 768 + 768 * 3072 + 768) + 2 * 50304 * 768,
        "all-gather": 4 * 16 * 8}
    assert hlo_count.array_bytes("(s4[10]{0}, pred[3,3]{1,0})") == [5, 9]
    assert hlo_count.collective_bytes("%f = f32[2]{0} add(%a, %b)") == {}
