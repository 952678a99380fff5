"""End-to-end pallas routing test: a tiny GPT trains with the pallas
kernels force-enabled (interpret on CPU) as the LIVE code path —
layernorm, flash attention, and softmax-CE all route through
ops/pallas/ — and the first-step loss matches the dense path exactly.
(Compiled-mode TPU validation is chip_smoke.py's kernels phase.)"""
import numpy as np
import paddle_tpu as pt
from paddle_tpu import optim
from paddle_tpu.ops import pallas as pk
from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss


def test_pallas_routing_end_to_end():
    pk.set_enabled(True)   # force the pallas routing; auto_interpret -> CPU
    try:
        _run()
    finally:
        pk.set_enabled(None)


def _run():
    pt.seed(0)
    # shapes chosen to satisfy the pallas gates: L%128==0, D%64==0, V%128==0
    cfg = GPTConfig(vocab_size=512, hidden=128, layers=2, heads=2, max_seq=128,
                    dropout=0.0)
    model = GPT(cfg)
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=3e-3,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step = pt.TrainStep(model, opt, gpt_loss)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 128)).astype("int32")
    labels = np.roll(ids, -1, axis=1).astype("int32")

    losses = []
    for i in range(8):
        losses.append(float(np.asarray(step(ids, labels)._data)))
    print("losses:", [round(x, 3) for x in losses])
    assert all(np.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0] - 0.5, f"no learning: {losses[0]} -> {losses[-1]}"

    # parity: same model, pallas off, must agree on the loss value closely
    pk.set_enabled(False)
    pt.seed(0)
    model2 = GPT(cfg)
    opt2 = optim.AdamW(parameters=model2.parameters(), learning_rate=3e-3,
                       grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step2 = pt.TrainStep(model2, opt2, gpt_loss)
    l_dense = float(np.asarray(step2(ids, labels)._data))
    assert abs(l_dense - losses[0]) < 1e-2, (l_dense, losses[0])
    print(f"pallas-vs-dense first-step loss parity: {losses[0]:.4f} vs {l_dense:.4f}")
    print("DRIVE OK")
