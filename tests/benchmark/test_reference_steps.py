"""``reference/_common.py:train_steps`` holds its state leaf by leaf on the
host (PR 26): the same numbers as a whole-tree clip + AdamW written here,
whatever the micro-batch, the batch's parity or the devices; the seeded
weights made again from the seed are the first ones bit for bit."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny
from benchmark import correct, generate, harness
from benchmark.loops import train
from benchmark.reference import _common as ref_common

SEED = 2 ** 31 + 26
# BERT's steps after the first amplify a last-bit difference (a fusion, the
# order of a sum) some tenfold: its small leaves (pooler.b, nsp.w) read 2e-6
TOLERANCE = {"gpt2": 1e-6, "bert": 1e-5}


def _case(family, rows=None, steps=train.CHECKED_STEPS):
    """What ``train_steps`` takes, at test size: (its leading arguments as a
    function of fresh weights, the specs); ``rows`` cuts every batch."""
    cell = bench_tiny.cell(family)
    cfg, traffic = cell["config"], cell["traffic"]
    fam = harness.load_module("families", cfg["family"])
    specs = fam.reference.param_specs(cfg)
    pool = generate.pool(traffic, cfg["vocab_size"], SEED)
    batch = traffic["batch"]
    batches = [tuple(a[i * batch:i * batch + (rows or batch)] for a in pool)
               for i in range(steps)]
    index = ref_common.sample_index(specs)

    def follow(**how):
        return ref_common.train_steps(
            fam.reference.loss_part(cfg), fam.reference.denominators,
            ref_common.init_weights(specs, SEED), batches, cfg["recipe"],
            index, **how)

    return follow, (fam, cfg, specs, batches, index)


def _whole_tree(fam, cfg, specs, batches, index, micro=2):
    """Three steps of clip + AdamW over whole trees that stay on the device,
    from the published equations: the plain thing ``train_steps`` was before
    it had to fit beside a large model."""
    r, mm = cfg["recipe"], ref_common.matmul_of("float32")
    b1, b2, lr = r["beta1"], r["beta2"], r["learning_rate"]
    grad = jax.jit(jax.value_and_grad(fam.reference.loss_part(cfg)),
                   static_argnums=3)
    first = {k: w.astype(jnp.float32)
             for k, w in ref_common.init_weights(specs, SEED).items()}
    p, m, v = dict(first), *({k: jnp.zeros_like(w) for k, w in first.items()}
                             for _ in range(2))
    out = {"losses": []}
    for t, batch in enumerate(batches, start=1):
        denoms, loss, g = fam.reference.denominators(batch), 0.0, None
        for lo in range(0, len(batch[0]), micro):
            l, part = grad(p, tuple(a[lo:lo + micro] for a in batch), denoms,
                           mm)
            loss, g = loss + l, part if g is None else \
                jax.tree_util.tree_map(jnp.add, g, part)
        out["losses"].append(float(loss))
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        g = {k: x * (r["clip_global_norm"] /
                     jnp.maximum(norm, r["clip_global_norm"]))
             for k, x in g.items()}
        if t == 1:
            out["grad_norms"] = {k: float(jnp.linalg.norm(x.reshape(-1)))
                                 for k, x in g.items()}
            out["grad_sample"] = {k: np.asarray(x.reshape(-1)[index[k]])
                                  for k, x in g.items()}
        t = jnp.float32(t)   # the bias corrections in float32, as the program's
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
        v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in g}
        p = {k: p[k] - lr * (m[k] / (1 - b1 ** t)) /
             (jnp.sqrt(v[k] / (1 - b2 ** t)) + r["epsilon"]) -
             lr * r["weight_decay"] * p[k] for k in g}
    out["delta_norms"] = {k: float(jnp.linalg.norm((p[k] - first[k])
                                                   .reshape(-1))) for k in p}
    return out


def _same(got, want, family="gpt2"):
    """Every number ``correct`` compares, and every leaf's own norms where
    the gradient is more than rounding noise."""
    tolerance = TOLERANCE[family]
    numbers = correct.compare(got, want)
    assert all(value <= tolerance for value, _ in numbers.values()), numbers
    assert got["losses"] == pytest.approx(want["losses"], rel=tolerance)
    floor = correct.NULL_GRADIENT * np.median(list(want["grad_norms"].values()))
    for kind in ("grad_norms", "delta_norms"):
        for k, w in want[kind].items():
            if want["grad_norms"][k] > floor:
                assert got[kind][k] == pytest.approx(w, rel=10 * tolerance), \
                    (kind, k)


@pytest.mark.parametrize("family", ["gpt2", "bert"])
def test_leaf_by_leaf_on_the_host_is_the_whole_tree_on_the_device(family):
    follow, case = _case(family)
    got = follow(micro=2)
    _same(got, _whole_tree(*case), family)
    assert set(got["grad_sample"]) == set(got["grad_norms"]) == \
        set(got["delta_norms"]) == {name for name, _, _ in case[2]}


@pytest.mark.parametrize("family", ["gpt2", "bert"])
def test_one_row_micro_batches_give_the_two_row_result(family):
    follow, _ = _case(family)
    _same(follow(micro=1), follow(micro=2), family)


@pytest.mark.parametrize("rows", [1, 7])
def test_a_batch_of_one_row_or_an_odd_count_is_followed(rows):
    follow, case = _case("gpt2", rows=rows)
    want = _whole_tree(*case)
    _same(follow(micro=2), want)
    _same(follow(micro=1), want)


@pytest.mark.parametrize("rows", [7, 8])
def test_four_devices_give_the_one_device_result(rows):
    """Four devices at two rows each take eight rows at a time: the tiny
    cell's eight fill them, and of seven none does, so they follow in
    micro-batches that every device computes alike."""
    follow, _ = _case("gpt2", rows=rows, steps=2)
    _same(follow(micro=2, devices=jax.devices()[:4]), follow(micro=2))


def test_train_steps_takes_the_seeded_leaves_one_by_one():
    _, (fam, cfg, specs, batches, index) = _case("gpt2", steps=1)
    weights = ref_common.init_weights(specs, SEED)
    ref_common.train_steps(fam.reference.loss_part(cfg),
                           fam.reference.denominators, weights, batches,
                           cfg["recipe"], index)
    assert weights == {}


@pytest.mark.parametrize("tokens,rows", [(128, 2), (512, 2), (1024, 2),
                                         (2048, 2), (4096, 1), (8192, 1)])
def test_a_micro_batch_is_two_rows_or_4096_tokens(tokens, rows):
    assert train.micro_rows(tokens) == rows


@pytest.mark.parametrize("family", ["gpt2", "bert"])
def test_the_weights_made_again_from_the_seed_are_bit_equal(family):
    cfg = bench_tiny.cell(family)["config"]
    specs = harness.load_module("families", cfg["family"]) \
        .reference.param_specs(cfg)
    first = ref_common.init_weights(specs, SEED)
    again = ref_common.init_weights(specs, SEED)
    other = ref_common.init_weights(specs, SEED + 1)
    for name, w in first.items():
        bits = np.asarray(w.view(jnp.uint16))
        assert np.array_equal(bits, np.asarray(again[name].view(jnp.uint16)))
    assert any(not np.array_equal(np.asarray(w), np.asarray(other[name]))
               for name, w in first.items())


def test_the_window_runs_without_the_seeded_weights(monkeypatch):
    """The loop drops ``Setup.weights`` before the window and the reference
    gets them made again: the run is still correct."""
    seen = {}
    set_up, window = train.set_up, train.timed_window

    def keep(cell, seed):
        seen["setup"] = set_up(cell, seed)
        return seen["setup"]

    def look(loop, seconds, compiles):
        seen["weights_in_window"] = seen["setup"].weights
        return window(loop, seconds, compiles)

    monkeypatch.setattr(train, "set_up", keep)
    monkeypatch.setattr(train, "timed_window", look)
    lines = []
    result = train.run(bench_tiny.cell("gpt2"), bench_tiny.run_args(11),
                       time.perf_counter(), lines.append, lambda window: {})
    assert seen["weights_in_window"] is None
    assert result["correct"], lines
    assert any("bytes_in_use" in l for l in lines)
