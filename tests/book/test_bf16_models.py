"""bf16 train-step regression tests: the standard TPU recipe of the benchmark's
cells (bf16 params + f32 master weights via multi_precision) must work for both
vision (conv/BN chains) and transformer models.

Guards the round-2 bug where ``preferred_element_type`` made bf16 convs
return f32 (and, once cast back, broke the conv vjp) so every stacked bf16
conv net crashed (ref recipe: contrib/mixed_precision/fp16_lists.py:20).
"""
import numpy as np

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
import paddle_tpu.optim as optim
from paddle_tpu.models.vision import resnet18
from paddle_tpu.models.nlp.bert import (BertForPretraining, bert_tiny,
                                        bert_pretrain_loss)


def test_resnet_bf16_train_step():
    pt.seed(0)
    model = resnet18(num_classes=4)
    model.bfloat16()
    opt = optim.Momentum(learning_rate=1e-2, momentum=0.9,
                         parameters=model.parameters(), multi_precision=True)
    step = pt.TrainStep(
        model, opt,
        lambda m, x, y: F.cross_entropy(
            m(x.astype("bfloat16")).astype("float32"), y))
    rng = np.random.RandomState(0)
    x = rng.randn(8, 3, 32, 32).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype("int64")
    losses = [float(step(x, y)) for _ in range(3)]
    assert np.isfinite(losses).all(), losses
    # params stay bf16; the f32 master copies live in the optimizer state
    assert all(str(p.dtype) == "bfloat16" for p in model.parameters())


def test_resnet_bf16_forward_dtype():
    pt.seed(0)
    model = resnet18(num_classes=4)
    model.bfloat16()
    model.eval()
    x = pt.to_tensor(np.random.randn(2, 3, 32, 32).astype(np.float32))
    out = model(x.astype("bfloat16"))
    assert str(out.dtype) == "bfloat16", out.dtype


def test_bert_bf16_train_step():
    pt.seed(0)
    cfg = bert_tiny(dropout=0.0)
    model = BertForPretraining(cfg)
    model.bfloat16()
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=1e-4,
                      multi_precision=True,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step = pt.TrainStep(model, opt, bert_pretrain_loss)
    rng = np.random.RandomState(0)
    B, L = 2, 32
    ids = rng.randint(0, cfg.vocab_size, (B, L)).astype("int32")
    tt = np.zeros((B, L), "int32")
    am = np.ones((B, L), "int32")
    mlm = np.where(rng.rand(B, L) < 0.15, ids, -100).astype("int32")
    nsp = rng.randint(0, 2, (B,)).astype("int32")
    losses = [float(step(ids, tt, am, mlm, nsp)) for _ in range(3)]
    assert np.isfinite(losses).all(), losses


def test_conv_transpose_bf16():
    """Transposed conv shares the fractionally-strided path; keep it bf16."""
    from paddle_tpu import ops

    pt.seed(0)
    x = pt.to_tensor(
        np.random.randn(2, 4, 8, 8).astype(np.float32)).astype("bfloat16")
    w = pt.to_tensor(
        np.random.randn(4, 6, 3, 3).astype(np.float32)).astype("bfloat16")
    out = ops.conv2d_transpose(x, w, stride=2, padding=1, output_padding=1)
    assert str(out.dtype) == "bfloat16"
    assert list(out.shape) == [2, 6, 16, 16]
