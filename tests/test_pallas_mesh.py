"""The three training kernels under a device mesh: the same
``ops.pallas.mesh_call`` shard_map wrapper the chip uses, exercised in
interpret mode on virtual CPU devices, with parity against the dense
path. Mosaic itself is not exercised here (chip_smoke.py does that);
the wiring — specs, resharding at the boundary, the backward through
shard_map — is."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import distributed as dist
from paddle_tpu import optim
from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss
from paddle_tpu.ops import pallas as pk


@pytest.fixture
def kernels_on(monkeypatch):
    pk.set_enabled(True)
    # L=128 is under the flash route's floor on the chip; the wiring under a
    # mesh is what is tested here, at a length the interpreter is quick at
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "MIN_STEP_SCORES", 128 * 128)
    yield
    pk.set_enabled(None)
    dist.set_mesh(None)


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return dist.init_mesh(axes, devices=jax.devices()[:n])


def test_shard_spec_follows_the_mesh(kernels_on):
    assert pk.shard_spec((8, 4, 128, 64), {0: pk.BATCH, 1: pk.HEADS}) == \
        (P(None, None, None, None), (8, 4, 128, 64))
    _mesh({"data": 2, "model": 2})
    assert pk.shard_spec((8, 4, 128, 64), {0: pk.BATCH, 1: pk.HEADS}) == \
        (P("data", "model", None, None), (4, 2, 128, 64))
    assert pk.shard_spec((1024, 512), {0: pk.ROWS}) == \
        (P(("data", "model"), None), (256, 512))
    # a dim an axis does not divide stays whole on every device
    assert pk.shard_spec((3, 4, 128, 64), {0: pk.BATCH, 1: pk.HEADS}) == \
        (P(None, "model", None, None), (3, 2, 128, 64))


def _losses(mesh_axes, steps=2):
    pt.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden=128, layers=2, heads=2,
                    max_seq=128, dropout=0.0)
    model = GPT(cfg)
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=3e-3,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    if mesh_axes is None:
        step = pt.TrainStep(model, opt, gpt_loss)
    else:
        step = dist.DistributedTrainStep(model, opt, gpt_loss,
                                         mesh=_mesh(mesh_axes))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 128)).astype("int32")
    labels = np.roll(ids, -1, axis=1).astype("int32")
    out = [float(np.asarray(step(ids, labels)._data)) for _ in range(steps)]
    return out, step


@pytest.mark.parametrize("axes", [{"data": 2}, {"data": 2, "model": 2}])
def test_gpt_step_with_kernels_under_mesh_matches_dense(kernels_on, axes):
    sharded, step = _losses(axes)
    # the kernels are in the program as shard_map bodies, not dropped
    lowered = step._compiled[next(iter(step._compiled))].lower(
        *step._arg_structs[next(iter(step._arg_structs))]).as_text()
    assert "shard_map" in lowered or "manual" in lowered
    pk.set_enabled(False)
    dist.set_mesh(None)
    dense, _ = _losses(None)
    np.testing.assert_allclose(sharded, dense, rtol=2e-3, atol=2e-3)


def test_partial_manual_shard_map_is_a_named_error(kernels_on):
    mesh = _mesh({"data": 2, "model": 2})
    x = jnp.ones((8, 128), jnp.float32)

    def body(x):
        return pk.shard_spec(x.shape, {0: pk.BATCH})[1][0] * x

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), axis_names={"data"})
    with pytest.raises(NotImplementedError, match="partial-manual"):
        jax.jit(f)(x)
    # all axes manual: the body already sees per-device shapes
    g = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))
    np.testing.assert_array_equal(jax.jit(g)(x), 4 * np.ones((8, 128)))
