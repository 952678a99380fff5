"""``correct`` at sizes a test can hold: the program's first three steps
against the float32 reference for both families, the fp8 control failing the
same limits, and a whole run (all but the look for a chip) coming out not
correct when the timed path is broken underneath."""
import time

import pytest

import bench_tiny
from benchmark import correct
from benchmark.loops import train

SEED = 2 ** 31 + 9  # the driver's seeds pass 32 signed bits


def _readings(family, seed):
    cell = bench_tiny.cell(family)
    su = train.set_up(cell, seed)
    got = train.program_readings(su.loop, su.model, su.step.optimizer,
                                 su.names, su.weights, su.index,
                                 cell["config"]["recipe"]["beta1"])
    batches = su.first_batches(train.CHECKED_STEPS, cell["traffic"]["batch"])

    def reference(precision):
        return train.reference_readings(su.family, cell, dict(su.weights),
                                        batches, su.index, precision)

    return got, reference


@pytest.fixture(scope="module", params=["gpt2", "bert"])
def readings(request):
    got, reference = _readings(request.param, SEED)
    return got, reference("float32"), reference("fp8")


def test_program_follows_the_reference(readings):
    got, want, _ = readings
    numbers = correct.compare(got, want)
    assert correct.judge(numbers, bench_tiny.LIMITS), numbers


def test_fp8_control_is_not_correct(readings):
    _, want, control = readings
    numbers = correct.compare(control, want)
    assert not correct.judge(numbers, bench_tiny.LIMITS), numbers
    assert numbers["grad_rel_err"][0] > bench_tiny.LIMITS["grad_rel_err"]


def test_reference_against_itself_is_exact(readings):
    _, want, _ = readings
    numbers = correct.compare(want, want)
    assert all(v == 0.0 for v, _ in numbers.values()), numbers


def _run(family, mesh=None):
    lines = []
    result = train.run(bench_tiny.cell(family, mesh), bench_tiny.run_args(7),
                       time.perf_counter(), lines.append, lambda window: {})
    return result, lines


@pytest.mark.parametrize("family", ["gpt2", "bert"])
def test_a_sound_run_is_correct(family):
    result, lines = _run(family)
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] >= 2
    e2e = result["end_to_end"]
    assert e2e["tokens_per_s_per_chip"] > 0 and e2e["step_ms_p90"] > 0
    # every number compared is printed beside its limit
    checks = [l for l in lines if l.startswith("check ")]
    assert {l.split()[1] for l in checks} >= set(bench_tiny.LIMITS)
    assert all("limit=" in l for l in checks)


@pytest.fixture
def mesh_restored():
    """``dist.init_mesh`` installs a process-wide mesh; put back what was
    there, so that later tests of this worker see none of ours."""
    from paddle_tpu.dist import env

    before = env.get_mesh()
    yield
    env.set_mesh(before)


def test_a_sound_data_parallel_run_is_correct(mesh_restored):
    result, lines = _run("gpt2", {"data": 4})
    assert result["correct"], lines


def test_a_step_that_keeps_its_state_is_not_correct(monkeypatch):
    """The timed path broken underneath: AdamW hands back the parameter and
    its slots unchanged."""
    from paddle_tpu import optim

    monkeypatch.setattr(optim.AdamW, "_update",
                        lambda self, p, g, s, lr: (p, s))
    result, lines = _run("gpt2")
    assert not result["correct"], lines
    assert any(l.startswith("check delta_norm_gap") and "OUTSIDE" in l
               for l in lines)


def test_a_lost_gradient_leaf_is_outside_berts_grad_norm_limit(monkeypatch):
    """The timed path broken underneath: one leaf's gradient never reaches
    AdamW. BERT's ``grad_norm_gap`` limit cannot judge precision (PERF.md
    section 2) and is there for this fault: held at the chip cell's own
    value, it has to come out OUTSIDE."""
    from benchmark import harness
    from paddle_tpu import optim

    cell = bench_tiny.cell("bert")
    cell["limits"]["grad_norm_gap"] = harness.load_json(
        "workloads", "bert_base_mlm_512.json")["limits"]["grad_norm_gap"]
    lost = (cell["config"]["hidden_size"], cell["config"]["intermediate_size"])
    update = optim.AdamW._update
    monkeypatch.setattr(
        optim.AdamW, "_update", lambda self, p, g, s, lr: update(
            self, p, g * 0 if g.shape == lost else g, s, lr))
    lines = []
    result = train.run(cell, bench_tiny.run_args(7), time.perf_counter(),
                       lines.append, lambda window: {})
    assert not result["correct"], lines
    assert any(l.startswith("check grad_norm_gap") and "OUTSIDE" in l and
               "ffn.in.w" in l for l in lines), lines


def test_a_configurations_program_group_reaches_the_programs_config():
    """What the program's own config takes and the source's file has no key
    for (``use_recompute``) goes through the family as it stands."""
    cell = bench_tiny.cell("gpt2")
    cell["config"]["program"] = {"use_recompute": True}
    su = train.set_up(cell, 3)
    assert su.model.cfg.use_recompute is True


def test_a_step_on_half_the_batch_is_not_correct(monkeypatch):
    """The timed path broken underneath: the loss sees only the first half
    of the rows."""
    from paddle_tpu.models.nlp import gpt

    whole = gpt.gpt_loss
    monkeypatch.setattr(
        gpt, "gpt_loss", lambda model, ids, labels: whole(
            model, ids[:ids.shape[0] // 2], labels[:labels.shape[0] // 2]))
    result, lines = _run("gpt2")
    assert not result["correct"], lines
