"""The program names its work: op, phase and kernel names in the compiled
step's ``op_name``s, and the program's spans in the profiler's own trace."""
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.core import dispatch
from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.ops import pallas as pk
from paddle_tpu.utils import profiler


def _tiny_step():
    model = GPT(GPTConfig(vocab_size=128, hidden=32, layers=1, heads=2,
                          max_seq=16, dropout=0.0))
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters(),
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    return pt.TrainStep(model, opt, gpt_loss)


def _batch(rows=2, length=16, vocab=128):
    ids = np.random.randint(0, vocab, (rows, length)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]+)"', hlo_text))


def _scopes(op_name):
    """The path's scope names, transformation wrappers taken off."""
    return set(re.split(r"[/()]+", op_name))


@pytest.fixture(scope="module")
def step_op_names():
    step = _tiny_step()
    step(*_batch())
    return _op_names(step.compiled().as_text())


@pytest.mark.parametrize("scope", ["forward", "backward", "optimizer",
                                   "sdpa", "layer_norm", "linear",
                                   "cross_entropy_hard"])
def test_compiled_step_names_phases_and_ops(step_op_names, scope):
    assert any(scope in _scopes(p) for p in step_op_names), scope


@pytest.mark.parametrize("op", ["sdpa", "layer_norm", "linear"])
def test_backward_ops_carry_the_forward_ops_name(step_op_names, op):
    # jax carries the scope inside the differentiated function to the
    # transposed ops: backward/transpose(jvp(<op>))/...
    backward = [p for p in step_op_names
                if "backward" in _scopes(p) and "transpose(" in p]
    assert any(f"jvp({op})" in p for p in backward), (op, backward[:5])


def test_optimizer_ops_are_not_under_forward_or_backward(step_op_names):
    under = [p for p in step_op_names if "optimizer" in _scopes(p)]
    assert under
    assert not any({"forward", "backward"} & _scopes(p) for p in under)


def _flash(causal):
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    return lambda: jax.grad(lambda x: pk.flash_attention(
        x, x, x, None, causal, None, 128, True).sum())(q)


def _layer_norm():
    x, g = jnp.ones((16, 128), jnp.float32), jnp.ones((128,), jnp.float32)
    return jax.grad(lambda a: pk.fused_layer_norm(a, g, g, 1e-5, True).sum())(x)


def _softmax_ce():
    logits, labels = jnp.ones((8, 256), jnp.float32), jnp.zeros((8,), jnp.int32)
    return jax.grad(lambda a: pk.softmax_cross_entropy(
        a, labels, -100, True).sum())(logits)


def _paged():
    q = jnp.ones((2, 2, 128), jnp.float32)
    pages = jnp.ones((4, 8, 2, 128), jnp.float32)
    table = jnp.zeros((2, 2), jnp.int32)
    lengths = jnp.array([3, 9], jnp.int32)
    return pk.paged_decode_attention(q, pages, pages, table, lengths,
                                     interpret=True)


@pytest.mark.parametrize("call,names", [
    (_flash(True), ["flash_fwd_causal", "flash_bwd_dq_causal",
                    "flash_bwd_dkv_causal"]),
    (_flash(False), ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    (_layer_norm, ["layer_norm_fwd", "layer_norm_bwd"]),
    (_softmax_ce, ["softmax_ce_fwd", "softmax_ce_bwd"]),
    (_paged, ["paged_attention"]),
], ids=["flash_causal", "flash", "layer_norm", "softmax_ce", "paged"])
def test_every_pallas_call_lowers_with_its_name(call, names):
    text = jax.jit(call).lower().as_text(debug_info=True)
    found = set()
    for p in re.findall(r'loc\("([^"]+)"', text):
        found |= _scopes(p)
    assert set(names) <= found, sorted(n for n in names if n not in found)
    if "flash_fwd" in names:   # the plain call is not the causal one's prefix
        assert not any(n.endswith("_causal") for n in found)


@pytest.fixture
def tracing_off():
    """Span tracing off for the test, whatever an earlier test left."""
    was_on = obs_trace.tracing_enabled()
    obs_trace.disable_tracing()
    yield
    obs_trace.clear_trace()
    if was_on:
        obs_trace.enable_tracing()


def test_profile_holds_the_programs_spans_on_the_host_plane(tmp_path,
                                                            tracing_off):
    step = _tiny_step()
    ids, labels = _batch(8)
    loader = pt.io.DataLoader(list(zip(ids, labels)), batch_size=2)
    step(*_batch())                      # compile outside the profile
    with profiler.profiler(log_dir=str(tmp_path)):
        for batch in loader:
            step(*batch)
    assert not obs_trace.tracing_enabled()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    data = jax.profiler.ProfileData.from_file(paths[-1])
    host = [p for p in data.planes if p.name == "/host:CPU"]
    assert host
    events = [e for line in host[0].lines for e in line.events]
    names = {e.name for e in events}
    assert {"trainstep.call", "trainstep.feed", "trainstep.execute",
            "trainstep.rebind", "dataloader.next"} <= names, sorted(names)[:40]
    calls = [dict(e.stats) for e in events if e.name == "trainstep.call"]
    assert len(calls) == 4
    assert sorted(int(c["step_num"]) for c in calls) == [1, 2, 3, 4]
    # and the host ring recorded the same spans on its own clock
    ring = {e["name"] for e in obs_trace.trace_events()}
    assert {"trainstep.execute", "dataloader.next"} <= ring


def test_disabled_span_is_the_shared_null_context(tracing_off):
    assert obs_trace.span("a", x=1) is obs_trace.span("b")
    with obs_trace.span("a"):
        pass


def test_eager_dispatch_enters_no_scope(monkeypatch):
    entered = []
    real = jax.named_scope

    def counting(name):
        entered.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", counting)
    x = pt.to_tensor(np.ones((2, 4), np.float32), stop_gradient=False)
    y = pt.nn.functional.relu(x * 2.0 + 1.0)
    y.sum().backward()
    assert entered == []
    # the same op under a trace does
    jax.jit(lambda a: dispatch.apply("relu", jax.nn.relu,
                                     pt.Tensor(a, _internal=True))._data)(
        jnp.ones(3))
    assert entered == ["relu"]
