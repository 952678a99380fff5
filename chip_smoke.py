"""chip_smoke: does the system start on the chip, and is what comes out right?

    python chip_smoke.py                 one TPU chip: device, kernels, train,
                                         the plain-residual MTP decoder
                                         the hybrid linear-attention, the
                                         window / full attention and the
                                         state-space decoders (tiny), serve,
                                         cache
    python chip_smoke.py --devices 4     four-chip host: device, train on one
                                         chip, then the same recipe sharded
                                         over {"data": 4} and {"data": 2,
                                         "model": 2}

One process (a chip belongs to one process), started from the root of the
checkout. It selects no platform: with no TPU it exits non-zero at once and
prints no result. Any phase that fails raises, and the traceback and a
non-zero exit are the report. On success the last line of stdout is
``{"ok": true, "device": {...}}`` with the device as jax reports it. Times and
memory printed on the way are information, not benchmark metrics.

``--rehearse-cpu`` walks the same phases at toy shapes with the kernels in the
Pallas interpreter, to debug this script without a chip. Every line it prints
starts ``platform=cpu``, it prints no result line, and nothing reaches it by
default or by failure. For ``--devices 4`` give it four host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import argparse
import contextlib
import gc
import json
import os
import re
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

# ---- sizes -----------------------------------------------------------------
# chip: GPT-2-small at the widths of the benchmark's gpt2-small, kernels at the
# shapes that step and the serve engine feed them. rehearsal: the smallest
# shapes that still pass every kernel's routing gate.
CHIP = dict(
    gpt=dict(vocab=50304, hidden=768, layers=12, heads=12, L=1024, B=16),
    # the test geometry, and a lane-filling one (16 heads x 128). Its vocab
    # is 4,096 and not a deployment's 32,000 because ServeEngine closes
    # over the model, so every bucket executable carries the weights as
    # constants: at 32,000 six compiles took 416 s of a cold smoke here.
    serve=[dict(vocab=32, heads=2, head_dim=8),
           dict(vocab=4096, heads=16, head_dim=128)],
    paged=[(2, 8, "float32"), (16, 128, "float32"), (16, 128, "bfloat16")],
    hc=(4, 4096, 3584),     # xing4_pretrain_ep8's streams: n, tokens, C
    # joyai's latent queries and laguna's full layers: heads, L, d, r, offset
    rope=[(32, 8192, 192, 64, 128), (56, 8192, 128, 64, 0)],
    ce_chunk=2048, steps=6)
REHEARSAL = dict(
    gpt=dict(vocab=512, hidden=128, layers=2, heads=2, L=128, B=8),
    serve=[dict(vocab=32, heads=2, head_dim=8)],
    paged=[(2, 8, "float32")],
    hc=(4, 128, 128),
    rope=[(2, 128, 192, 64, 128)],
    ce_chunk=512, steps=6)
MESHES = (({"data": 4}, 4), ({"data": 2, "model": 2}, 2))  # (axes, B multiple)

PLATFORM = jax.default_backend()
KERNELS = None  # ops.pallas.set_enabled() value in force: None = by backend


T0 = time.perf_counter()


def say(msg):
    print(f"platform={PLATFORM} t={time.perf_counter() - T0:4.0f}s {msg}",
          flush=True)


# ---- device ----------------------------------------------------------------
def phase_device(n_devices, rehearse):
    from importlib.metadata import version

    devs = jax.devices()
    say(f"[device] device_kind={devs[0].device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} jaxlib={version('jaxlib')} "
        f"libtpu={version('libtpu')}")
    if len(devs) < n_devices:
        raise SystemExit(f"--devices {n_devices} needs {n_devices} "
                         f"{PLATFORM} devices, found {len(devs)}")
    from paddle_tpu import runtime
    from paddle_tpu.obs.mfu import peak_flops

    if not rehearse:  # unknown device_kind raises: no invented peak
        say(f"[device] peak_bf16_flops={peak_flops(devs[0].device_kind):.3g}"
            " (obs.mfu.PEAK_FLOPS_BY_KIND)")
    say(f"[device] native_runtime={runtime.native_status()}")


# ---- kernels ---------------------------------------------------------------
def _rel_err(name, got, want):
    """Worst leaf of max|got - want| / max|want|: each leaf against its own
    scale, so a dead gradient leaf next to a large one cannot hide."""
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        if not bool(jnp.all(jnp.isfinite(g))):
            raise AssertionError(f"{name}: non-finite kernel output")
        worst = max(worst, float(jnp.max(jnp.abs(g - w))) / max(
            float(jnp.max(jnp.abs(w))), 1e-6))
    return worst


def _agree(name, worst, tol):
    if worst > tol:
        raise AssertionError(f"{name}: max rel err {worst:.4g} > {tol} vs "
                             "the dense reference")
    say(f"[kernels] {name}: ok max_rel_err={worst:.3g} (tol {tol})")


@contextlib.contextmanager
def _dense():
    """The repo's own dense branches are the references: the same op
    functions with the kernels switched off."""
    from paddle_tpu.ops import pallas as pk

    pk.set_enabled(False)
    try:
        yield
    finally:
        pk.set_enabled(KERNELS)


def phase_kernels(size, interpret):
    from paddle_tpu.nn.functional.attention import _sdpa
    from paddle_tpu.nn.functional.loss import _ce_hard
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.norm_ops import _layer_norm

    g = size["gpt"]
    B, H, L, D = g["B"], g["heads"], g["L"], g["hidden"] // g["heads"]
    N, E, V = B * L, g["hidden"], g["vocab"]
    rng = np.random.RandomState(0)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 24))

    def rand(*shape, dtype=jnp.bfloat16):  # made on the device, from a seed
        return jax.random.normal(next(keys), shape, dtype)

    def check(name, got, want, tol):
        _agree(name, _rel_err(name, got, want), tol)

    say(f"[kernels] interpret={interpret} flash=({B},{H},{L},{D}) "
        f"layer_norm=({N},{E}) softmax_ce=({N},{V}) "
        f"hyper_connection={size['hc']} rotary={size['rope']}")
    # weighted sums as losses: a plain sum makes the cotangent constant and
    # the true dx of layer_norm ~0, so any noise reads as 100% error
    # flash attention, causal, bf16 -- reference: _sdpa's dense branch
    q, k, v, w = rand(B, H, L, D), rand(B, H, L, D), rand(B, H, L, D), \
        rand(B, H, L, D, dtype=jnp.float32)
    scale = 1.0 / D ** 0.5

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, None, True, scale, None, interpret)

    def flash_ref(q, k, v):
        return _sdpa(q, k, v, None, None, scale=scale, is_causal=True,
                     dropout_p=0.0)

    def wsum(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    with _dense():
        want = jax.jit(flash_ref)(q, k, v)
        dwant = jax.jit(jax.grad(wsum(flash_ref), (0, 1, 2)))(q, k, v)
    check("flash_attention fwd", jax.jit(flash)(q, k, v), want, 0.03)
    check("flash_attention bwd",
          jax.jit(jax.grad(wsum(flash), (0, 1, 2)))(q, k, v), dwant, 0.05)

    # the same bodies over a sliding window's band (a query sees its last
    # L / 4 + 1 keys: no multiple of a block) -- reference: the dense
    # branch's band mask
    window = L // 4 + 1

    def banded(q, k, v):
        return pk.window_attention(q, k, v, window, scale, None, interpret)

    def banded_ref(q, k, v):
        return _sdpa(q, k, v, None, None, scale=scale, is_causal=True,
                     dropout_p=0.0, window=window)

    with _dense():
        want = jax.jit(banded_ref)(q, k, v)
        dwant = jax.jit(jax.grad(wsum(banded_ref), (0, 1, 2)))(q, k, v)
    check(f"window_attention w={window} fwd", jax.jit(banded)(q, k, v), want,
          0.03)
    check(f"window_attention w={window} bwd",
          jax.jit(jax.grad(wsum(banded), (0, 1, 2)))(q, k, v), dwant, 0.05)

    # the same kernels, not causal, a padding mask as their key bias (rows
    # of four lengths, one of them 0) -- reference: the dense branch's mask
    kept = jnp.asarray([L, L // 2 - 3, 0, L - 1] * B)[:B, None]
    bias = jnp.where(jnp.arange(L) < kept, 0.0, -1e30)[:, None].astype(
        jnp.float32)

    def masked(q, k, v):
        return pk.flash_attention(q, k, v, bias, False, scale, None,
                                  interpret)

    def masked_ref(q, k, v):
        return _sdpa(q, k, v, bias[:, None], None, scale=scale,
                     is_causal=False, dropout_p=0.0)

    with _dense():
        want = jax.jit(masked_ref)(q, k, v)
        dwant = jax.jit(jax.grad(wsum(masked_ref), (0, 1, 2)))(q, k, v)
    check("flash_attention key bias fwd", jax.jit(masked)(q, k, v), want,
          0.03)
    check("flash_attention key bias bwd",
          jax.jit(jax.grad(wsum(masked), (0, 1, 2)))(q, k, v), dwant, 0.05)
    del q, k, v, w, want, dwant

    # fused layer norm -- reference: _layer_norm's jnp branch
    x, gam, bet, w = rand(N, E), rand(E), rand(E), \
        rand(N, E, dtype=jnp.float32)

    def ln(x, gam, bet):
        return pk.fused_layer_norm(x, gam, bet, 1e-5, interpret)

    def ln_ref(x, gam, bet):
        return _layer_norm(x, gam, bet, epsilon=1e-5, begin_norm_axis=1)

    with _dense():
        want = jax.jit(ln_ref)(x, gam, bet)
        dwant = jax.jit(jax.grad(wsum(ln_ref), (0, 1, 2)))(x, gam, bet)
    check("fused_layer_norm fwd", jax.jit(ln)(x, gam, bet), want, 0.03)
    check("fused_layer_norm bwd",
          jax.jit(jax.grad(wsum(ln), (0, 1, 2)))(x, gam, bet), dwant, 0.05)
    del x, w, want, dwant

    # softmax cross entropy at the LM-head shape -- reference: _ce_hard's
    # log_softmax branch, a row chunk at a time (rows are independent; the
    # dense backward at full size would not fit beside the kernel's)
    logits = rand(N, V)
    labels = jax.random.randint(next(keys), (N,), 0, V, jnp.int32)
    wr = rand(N, dtype=jnp.float32)

    def ce(x, y):
        return pk.softmax_cross_entropy(x, y, -100, interpret)

    def ce_ref(x, y):
        return _ce_hard(x, y, None, axis=-1, ignore_index=-100,
                        reduction="none", use_softmax=True,
                        label_smoothing=0.0)

    got = jax.jit(ce)(logits, labels)
    dgot = jax.jit(jax.grad(lambda x, y, w: jnp.sum(ce(x, y) * w)))(
        logits, labels, wr)
    ref = jax.jit(ce_ref)
    dref = jax.jit(jax.grad(lambda x, y, w: jnp.sum(ce_ref(x, y) * w)))
    c, fwd, bwd = size["ce_chunk"], 0.0, 0.0
    with _dense():
        for i in range(0, N, c):
            rows = slice(i, i + c)
            fwd = max(fwd, _rel_err("softmax_cross_entropy fwd", got[rows],
                                    ref(logits[rows], labels[rows])))
            bwd = max(bwd, _rel_err(
                "softmax_cross_entropy bwd", dgot[rows],
                dref(logits[rows], labels[rows], wr[rows])))
    _agree("softmax_cross_entropy fwd", fwd, 0.03)
    _agree("softmax_cross_entropy bwd", bwd, 0.05)
    del logits, got, dgot

    # the multi-stream residual's kernels, one sublayer's three ops with the
    # published strong diagonal, bf16 -- reference: the ops' jnp branches
    from paddle_tpu.nn.functional.decoder import _hc_maps, _hc_mix, _hc_read

    n, T, C = size["hc"]
    kk = 2 * n + n * n
    xs, ys = rand(n, 1, T, C), rand(1, T, C)
    phi, alpha, hbias = (0.02 * rand(n * C, kk, dtype=jnp.float32)).astype(
        jnp.bfloat16), jnp.ones((3,), jnp.bfloat16), 0.1 * rand(kk)
    wx = rand(n, 1, T, C, dtype=jnp.float32)
    assert pk.hc_route(xs.shape, xs.dtype) is not None, "hc_route refused"

    def sublayer(x, y, phi, alpha, hbias):
        pre, post, res = _hc_maps(
            x, phi, alpha, hbias, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
            alpha_scale=0.01, res_offset=4.0, norm_eps=1e-6)
        h = _hc_read(x, pre)
        return _hc_mix(x, y + h, post, res), (pre, post, res, h)

    def hc_loss(*a):
        out, maps = sublayer(*a)
        return jnp.sum(out.astype(jnp.float32) * wx) + sum(
            jnp.sum(m.astype(jnp.float32)) for m in maps)

    hc_args = (xs, ys, phi, alpha, hbias)
    with _dense():
        want = jax.jit(sublayer)(*hc_args)
        dwant = jax.jit(jax.grad(hc_loss, (0, 1, 2, 3, 4)))(*hc_args)
    check("hyper_connection fwd", jax.jit(sublayer)(*hc_args), want, 0.01)
    check("hyper_connection bwd",
          jax.jit(jax.grad(hc_loss, (0, 1, 2, 3, 4)))(*hc_args), dwant, 0.02)
    del xs, ys, wx, want, dwant

    # the rotary kernel, forward and its backward (the same body) -- reference:
    # the op's jnp body
    from paddle_tpu.nn.functional.decoder import _rotary, rotary_cos_sin

    for heads, length, d, r, offset in size["rope"]:
        xr, gr = rand(1, heads, length, d), rand(1, heads, length, d)
        cos, sin = (jnp.asarray(t) for t in rotary_cos_sin(length, r, 1e4))
        assert pk.rotary_route(xr.shape, xr.dtype, r, offset) is not None, \
            "rotary_route refused"

        def turn(x, g):
            out, vjp = jax.vjp(
                lambda t: _rotary(t, cos, sin, offset=offset), x)
            return out, vjp(g)[0]

        with _dense():
            want = jax.jit(turn)(xr, gr)
        check(f"rotary {r} of {d} from {offset}, fwd + bwd",
              jax.jit(turn)(xr, gr), want, 0.01)
    del xr, gr, want

    # paged decode attention at the serve geometries -- reference:
    # dense_decode_reference over the same histories laid out contiguously
    for heads, dim, dtype in size["paged"]:
        page, pool, maxp = 16, 64, 5
        lengths = np.array([1, 16, 17, 40, 64, 3, 33, 80], np.int32)
        Bq = len(lengths)
        kd = rng.randn(Bq, maxp * page, heads, dim).astype(np.float32)
        vd = rng.randn(Bq, maxp * page, heads, dim).astype(np.float32)
        kp = np.zeros((pool, page, heads, dim), np.float32)
        vp = np.zeros((pool, page, heads, dim), np.float32)
        table = np.zeros((Bq, maxp), np.int32)
        free = list(rng.permutation(np.arange(1, pool)))
        for b in range(Bq):
            for p in range(-(-int(lengths[b]) // page)):
                pid = table[b, p] = free.pop()
                lo, hi = p * page, min((p + 1) * page, int(lengths[b]))
                kp[pid, :hi - lo], vp[pid, :hi - lo] = kd[b, lo:hi], \
                    vd[b, lo:hi]
        qd = jnp.asarray(rng.randn(Bq, heads, dim), dtype)
        got = jax.jit(lambda *a: pk.paged_decode_attention(
            *a, interpret=interpret))(
                qd, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
                jnp.asarray(table), jnp.asarray(lengths))
        # the kernel contracts in exact f32 on the VPU; hold the reference's
        # einsums to f32 too instead of the MXU's default bf16 passes
        with jax.default_matmul_precision("highest"):
            want = pk.dense_decode_reference(
                qd, jnp.asarray(kd, dtype), jnp.asarray(vd, dtype),
                jnp.asarray(lengths))
        check(f"paged_decode_attention {heads}x{dim} {dtype}", got, want,
              0.03 if dtype == "bfloat16" else 1e-3)


# ---- train -----------------------------------------------------------------
def _gpt_small(g, B, mesh=None):
    """The benchmark's training recipe: bf16 params, f32 master AdamW, clip."""
    import paddle_tpu as pt
    from paddle_tpu import distributed as dist
    from paddle_tpu import optim
    from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss

    pt.seed(0)
    cfg = GPTConfig(vocab_size=g["vocab"], hidden=g["hidden"],
                    layers=g["layers"], heads=g["heads"], max_seq=g["L"],
                    dropout=0.0)
    model = GPT(cfg)
    model.bfloat16()
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=1e-4,
                      multi_precision=True,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    if mesh is None:
        step = pt.TrainStep(model, opt, gpt_loss)
    else:
        step = dist.DistributedTrainStep(model, opt, gpt_loss, mesh=mesh)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, g["vocab"], (B, g["L"])).astype("int32")
    return model, step, (ids, np.roll(ids, -1, axis=1).astype("int32"))


def _peaks():
    """peak_bytes_in_use per device; None where the backend reports none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def _train(tag, size, B, mesh=None):
    """>= 5 steps on one fixed batch. Returns the model, the step, its
    compiled HLO text and the bytes one device needs to run it (arguments
    + temporaries, from the executable's own memory_analysis)."""
    model, step, batch = _gpt_small(size["gpt"], B, mesh)
    t0 = time.perf_counter()
    losses = [jax.block_until_ready(step(*batch)._data)]
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(size["steps"] - 1):
        losses.append(step(*batch)._data)
    jax.block_until_ready(losses[-1])
    step_ms = (time.perf_counter() - t0) / (size["steps"] - 1) * 1e3
    losses = [float(x) for x in losses]
    say(f"[{tag}] losses={[round(x, 4) for x in losses]}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{tag}: loss did not fall: {losses}")
    exe = step.compiled()
    text, mem = exe.as_text(), exe.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    n_kernels = len(re.findall(r'custom_call_target="tpu_custom_call"', text))
    say(f"[{tag}] info: first_step_incl_compile_s={compile_s:.1f} "
        f"steady_step_ms={step_ms:.1f} pallas_calls_in_step={n_kernels} "
        f"step_bytes_per_device={need} (args {mem.argument_size_in_bytes} + "
        f"temps {mem.temp_size_in_bytes}) peak_bytes_in_use={_peaks()}")
    if PLATFORM == "tpu" and n_kernels == 0:
        raise AssertionError(f"{tag}: the compiled step holds no Pallas "
                             "call: the kernels were routed around")
    return model, step, text, need


def phase_plain_mtp():
    """The plain-residual latent-attention decoder with routed experts and
    its MTP module (``LatentMoE(streams=1, mtp_layers=1)``, a tiny preset):
    a forward pass and ``TrainStep`` calls under ``use_recompute``."""
    import paddle_tpu as pt
    from paddle_tpu import optim
    from paddle_tpu.models.nlp import latent_moe as lm

    pt.seed(0)
    # widths of whole 128-lane columns: the grouped products of the routed
    # experts (megablox) do not lower for the chip at the preset's 64 and 32
    model = lm.LatentMoE(lm.latent_moe_tiny(
        streams=1, mtp_layers=1, rope_scaling=None, use_recompute=True,
        hidden=128, expert_width=128, dense_width=256))
    ids = np.random.default_rng(0).integers(0, 256, (2, 129)).astype(np.int32)
    main, extra = model.forward_mtp(pt.to_tensor(ids[:, :-1]),
                                    pt.to_tensor(ids[:, 1:]))
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        grad_clip=optim.ClipGradByGlobalNorm(1.0)), lm.latent_moe_loss)
    losses = [float(step(ids[:, :-1], ids[:, 1:]).numpy()) for _ in range(3)]
    if main.shape != extra.shape or not losses[-1] < losses[0]:
        raise AssertionError(f"plain_mtp: {main.shape} {extra.shape} {losses}")
    say(f"[plain_mtp] LatentMoE streams=1 mtp_layers=1: logits {main.shape} "
        f"twice, TrainStep losses={[round(x, 4) for x in losses]}, "
        f"loss terms (lm, mtp)={np.asarray(model.loss_terms._data).round(4)}")


def phase_hybrid():
    """The hybrid decoder (``HybridMoE``, a tiny preset): three gated-delta-
    rule linear-attention layers to one gated grouped-query softmax layer,
    routed experts in each, heads 2-3 of 4 held: a forward pass and
    ``TrainStep`` calls under ``use_recompute``, in bfloat16."""
    import paddle_tpu as pt
    from paddle_tpu import optim
    from paddle_tpu.models.nlp import hybrid_moe as hm
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss

    pt.seed(0)
    # widths of whole 128-lane columns, as [plain_mtp]'s; rows of 200: three
    # chunks of 64 and a part of one
    model = hm.HybridMoE(hm.hybrid_moe_tiny(
        hidden=128, expert_width=128, head_dim=64, linear_head_dim=64,
        chunk=64, heads_held=2, first_head=2, use_recompute=True))
    model.bfloat16()
    ids = np.random.default_rng(0).integers(0, 256, (2, 201)).astype(np.int32)
    logits = model(pt.to_tensor(ids[:, :-1]))
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True, grad_clip=optim.ClipGradByGlobalNorm(1.0)),
        latent_moe_loss)
    losses = [float(step(ids[:, :-1], ids[:, 1:]).numpy()) for _ in range(3)]
    low, beta = (float(x) for x in model.linear_attn_stats._data)
    if logits.shape != [2, 200, 256] or not losses[-1] < losses[0] or \
            not -64 < low < -24:
        raise AssertionError(f"hybrid: {logits.shape} {losses} {low} {beta}")
    say(f"[hybrid] HybridMoE 3 linear : 1 softmax, heads 2-3 of 4: logits "
        f"{logits.shape}, TrainStep losses={[round(x, 4) for x in losses]}, "
        f"chunk log-decay min {low:.1f}, mean beta {beta:.3f}")


def phase_laguna():
    """The window / full attention decoder (``LagunaMoE``, a tiny preset):
    a dense layer and two with softmax-routed experts, full, sliding,
    sliding attention under a gate a head, experts 4-7 of 8 held: a forward
    pass and ``TrainStep`` calls under ``use_recompute``, in bfloat16; on the
    chip the compiled step must hold the windowed kernels."""
    import paddle_tpu as pt
    from paddle_tpu import optim
    from paddle_tpu.models.nlp import laguna_moe as lg
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss

    pt.seed(0)
    # widths of whole 128-lane columns, as [plain_mtp]'s; rows of 512 under a
    # window of 128: the route's floor is a block of 256 x 256 scores
    model = lg.LagunaMoE(lg.laguna_moe_tiny(
        layers=3, hidden=128, expert_width=128, dense_width=256, head_dim=64,
        window=128, experts_held=4, first_expert=4, use_recompute=True))
    model.bfloat16()
    ids = np.random.default_rng(0).integers(0, 256, (2, 513)).astype(np.int32)
    logits = model(pt.to_tensor(ids[:, :-1]))
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True, grad_clip=optim.ClipGradByGlobalNorm(1.0)),
        latent_moe_loss)
    losses = [float(step(ids[:, :-1], ids[:, 1:]).numpy()) for _ in range(3)]
    gate = float(model.attn_stats._data[0])
    text = step.compiled().as_text()
    held = sorted(set(re.findall(r"%(swa_\w+?|flash_\w+?)(?:\.\d+)? = ", text)))
    if logits.shape != [2, 512, 256] or not losses[-1] < losses[0] or \
            not 0.4 < gate < 0.6 or (PLATFORM == "tpu" and not {
                "swa_fwd_w128", "swa_bwd_dq_w128", "swa_bwd_dkv_w128",
                "flash_fwd_causal"} <= set(held)):
        raise AssertionError(f"laguna: {logits.shape} {losses} {gate} {held}")
    say(f"[laguna] LagunaMoE full, sliding, sliding (window 128), experts "
        f"4-7 of 8: logits {logits.shape}, TrainStep losses="
        f"{[round(x, 4) for x in losses]}, mean head gate {gate:.3f}, "
        f"kernels {held}")


def phase_ssm_hybrid(rehearse):
    """The hybrid state-space decoder (``SSMHybrid``, the ``granite4h``
    family at a tiny size): mamba, attention, mamba under the four
    multipliers and a tied head, three ``TrainStep`` calls under
    ``use_recompute`` in bfloat16 against the plain reference's three steps;
    then ``ssm_chunk`` alone at the published head sizes (64 heads of 64 over
    a state of 128, chunks of 256, a row of 8,192), through the
    ``ssm_scan_*`` kernels on the chip, against the token-by-token
    recurrence, result and all six gradients, with A and Delta drawn as
    Mamba-2 draws them, so that state is carried over the chunks."""
    from benchmark import harness
    from benchmark.reference import _common as rc
    from benchmark.reference import granite4h as ref

    # widths of whole 128-lane columns, as [plain_mtp]'s; rows of 200: three
    # chunks of 64 and a part of one
    cfg = harness.load_json("configs", "granite-4.0-h-micro.json")
    cfg.update(hidden_size=128, intermediate_size=256,
               shared_intermediate_size=256, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_attention_heads=2, num_key_value_heads=1, mamba_n_heads=4,
               mamba_d_head=64, mamba_chunk_size=64, vocab_size=256)
    cfg["recipe"] = dict(cfg["recipe"], learning_rate=1e-3)
    family = harness.load_module("families", "granite4h")
    specs = ref.param_specs(cfg)
    model, step = family.build(cfg, rc.init_weights(specs, 0), None)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 201)).astype(np.int32)
    batches = [(ids[:, :-1], ids[:, 1:])] * 3
    losses = [float(step(*b).numpy()) for b in batches]
    low, dt_mean = (float(x) for x in model.state_space_stats._data)
    want = rc.train_steps(ref.loss_part(cfg), ref.denominators,
                          rc.init_weights(specs, 0), batches, cfg["recipe"],
                          rc.sample_index(specs))["losses"]
    gaps = [abs(a - b) / b for a, b in zip(losses, want)]
    if not max(gaps) < 5e-3 or not losses[-1] < losses[0] or \
            not -64 < low < -24:
        raise AssertionError(f"ssm_hybrid: {losses} {want} {low} {dt_mean}")
    say(f"[ssm_hybrid] SSMHybrid mamba, attention, mamba, tied head: "
        f"TrainStep losses={[round(x, 4) for x in losses]} reference "
        f"{[round(x, 4) for x in want]}, chunk log-decay min {low:.1f}, "
        f"mean Delta {dt_mean:.3f}")

    from paddle_tpu.nn.functional import state_space as ss
    from paddle_tpu.ops import pallas as pk

    length, h, p, n, chunk = (512, 4, 16, 16, 64) if rehearse else \
        (8192, 64, 64, 128, 256)
    x, b, c, weight = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                       for shape in ((1, length, h, p), (1, length, n),
                                     (1, length, n), (1, length, h, p)))
    dt = jnp.exp(jnp.asarray(rng.uniform(np.log(0.001), np.log(0.1),
                                         (1, length, h)), jnp.float32))
    a_log = jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32))
    d = jnp.ones((h,), jnp.float32)
    operands = (x, dt, a_log, b, c, d)
    # on the chip the scan is the two kernels (ops.pallas.ssm_scan); the
    # rehearsal's sizes are outside their route and run the lax.scan
    routed = pk.ssm_scan_route(x.shape, x.dtype, n, chunk) is not None
    if routed == rehearse:
        raise AssertionError(f"ssm_scan_route: {routed}")

    def through(fn):
        def loss(*xs):
            y, low = fn(*xs)
            return jnp.sum(y.astype(jnp.float32) *
                           weight.astype(jnp.float32)), (y, low)
        return jax.jit(jax.value_and_grad(loss, tuple(range(6)),
                                          has_aux=True))

    def stepped(x, dt, a_log, b, c, d):
        xf, bf, cf = (a.astype(jnp.float32) for a in (x, b, c))
        return ref.recurrence(xf, dt, -jnp.exp(a_log), bf, cf) + \
            d[:, None] * xf, None

    t0 = time.perf_counter()
    (_, (got, low)), grads = through(
        lambda *xs: ss._ssm_chunk(*xs, chunk=chunk))(*operands)
    got = np.asarray(got, np.float32)
    took = time.perf_counter() - t0
    (_, (want, _)), want_grads = through(stepped)(*operands)
    want = np.asarray(want)
    scale, err = np.abs(want).max(), np.abs(got - want).max()
    # bfloat16 outputs and bfloat16 gradients of x, B, C: 2^-8 of the value,
    # against the largest; float32 sums over 8,192 tokens for the others
    worst = {}
    for name, g, w in zip(("x", "dt", "a_log", "B", "C", "D"), grads,
                          want_grads):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        worst[name] = float(np.abs(g - w).max() / np.abs(w).max())
        if not np.isfinite(g).all() or not worst[name] <= (
                1e-2 if name in "xBC" else 1e-3):
            raise AssertionError(f"ssm_chunk: d{name} off by {worst[name]}")
    if not err <= 1e-2 * scale or not np.isfinite(got).all():
        raise AssertionError(f"ssm_chunk: err {err} scale {scale}")
    say(f"[ssm_hybrid] ssm_chunk {h} heads x {p} x {n}, chunk {chunk}, "
        f"{length} tokens, {'ssm_scan kernels' if routed else 'lax.scan'} "
        f"against the recurrence: max err {err:.3g} of {scale:.3g}, "
        f"gradients off by "
        f"{', '.join(f'd{k} {v:.2g}' for k, v in worst.items())} of their "
        f"largest, chunk log-decay min {float(low):.2f}, "
        f"info: first call {took:.1f}s")


def phase_train(size):
    g = size["gpt"]
    say(f"[train] GPT layers={g['layers']} hidden={g['hidden']} "
        f"heads={g['heads']} vocab={g['vocab']} L={g['L']} B={g['B']} "
        "bf16 + f32-master AdamW + clip, pt.TrainStep")
    need = _train("train", size, g["B"])[3]
    gc.collect()  # frees the step's arrays before another phase allocates
    return need, _peaks()[0]


def phase_train_sharded(size, one_chip_need, one_chip_peak):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import distributed as dist

    g = size["gpt"]
    for axes, per in MESHES:
        B = g["B"] * per
        tag = "train " + "x".join(f"{k}{v}" for k, v in axes.items())
        mesh = dist.init_mesh(axes, devices=jax.devices()[:4])
        say(f"[{tag}] same recipe, dist.DistributedTrainStep, B={B}")
        model, step, text, need = _train(tag, size, B, mesh)
        # the program itself must show the work spread over the devices
        p = next(iter(model.parameters()))._data
        on = {s.device for s in p.addressable_shards}
        if len(on) != 4:
            raise AssertionError(f"{tag}: parameters sit on {len(on)} devices")
        ids = step._arg_structs[next(reversed(step._arg_structs))][5][0]
        shard = ids.sharding.shard_shape(ids.shape)
        if len(ids.sharding.device_set) != 4 or ids.sharding.spec != \
                P("data") or shard[0] != B // axes["data"]:
            raise AssertionError(f"{tag}: batch laid out as {ids.sharding}")
        if not step.collective_profile()["counts"].get("all-reduce"):
            raise AssertionError(f"{tag}: no all-reduce in the compiled step")
        # an all-gather whose result is a whole global-batch activation
        # (>= B*L*hidden elements, with B or B*L among its dims) would put
        # every device back on the global batch
        for shape in re.findall(r"= \w+\[([\d,]+)\]\S* all-gather", text):
            dims = [int(d) for d in shape.split(",")]
            if int(np.prod(dims)) >= B * g["L"] * g["hidden"] and \
                    {B, B * g["L"]} & set(dims):
                raise AssertionError(f"{tag}: all-gather result [{shape}] "
                                     "carries the global batch")
        # no device may need much more than the one-chip step does: by the
        # executable's own accounting, and by what the runtime saw in use
        ratios = {"step_bytes": need / one_chip_need}
        if one_chip_peak is not None:
            ratios["peak_bytes_in_use"] = max(_peaks()[:4]) / one_chip_peak
        say(f"[{tag}] per-device / one-chip: " + " ".join(
            f"{k}={v:.2f}" for k, v in ratios.items()))
        if max(ratios.values()) > 1.3:
            raise AssertionError(f"{tag}: a device carries more than 1.3x "
                                 f"the one-chip step: {ratios}")
        say(f"[{tag}] ok: params and batch on 4 devices, batch shard "
            f"{shard}, all-reduce present, no global-batch all-gather")
        del model, step
        dist.set_mesh(None)
        gc.collect()


# ---- serve -----------------------------------------------------------------
def phase_serve(size):
    from paddle_tpu.serving import PagedKVCache, ServeEngine, TinyLM

    rng = np.random.RandomState(0)
    for geo in size["serve"]:
        model = TinyLM(vocab_size=geo["vocab"], num_heads=geo["heads"],
                       head_dim=geo["head_dim"], seed=0)
        cache = PagedKVCache(64, 8, geo["heads"], geo["head_dim"])
        eng = ServeEngine(model, cache)
        if eng._interpret is not (PLATFORM == "cpu"):
            raise AssertionError(f"serve: interpret={eng._interpret} on "
                                 f"{PLATFORM}")
        # eight requests of mixed prompt lengths; 6-8 new tokens take every
        # context across an 8-token page boundary, and all eight decode
        # together (batch bucket 8) until the short ones finish. Contexts
        # stay within 5..20 tokens because the oracle below runs op by op
        # and compiles once per distinct length.
        reqs = [eng.submit([int(t) for t in rng.randint(0, geo["vocab"], n)],
                           max_new_tokens=m)
                for n, m in zip((5, 6, 7, 8, 9, 11, 13, 15),
                                (8, 8, 8, 8, 6, 6, 6, 6))]
        # TinyLM is f32 and the comparison is token-exact: keep the MXU's
        # default bf16 passes from flipping a near-tie between the engine's
        # fused step and the oracle's op-by-op one
        with jax.default_matmul_precision("highest"):
            steps = eng.run()
            want = [model.reference_generate(r.prompt, r.max_new_tokens)
                    for r in reqs]
        if len(eng.finished) != len(reqs):
            raise AssertionError(f"serve: {len(eng.finished)}/{len(reqs)} "
                                 "requests finished")
        for r, w in zip(reqs, want):
            if r.generated != w:
                raise AssertionError(
                    f"serve {geo}: request {r.rid} generated {r.generated} "
                    f"!= reference {w}")
        buckets = sorted(eng._decode_fns)
        if not any(b == 8 for b, _ in buckets):
            raise AssertionError(f"serve: decode buckets {buckets} never "
                                 "reached batch 8")
        say(f"[serve] TinyLM vocab={geo['vocab']} {geo['heads']}x"
            f"{geo['head_dim']}: ok interpret={eng._interpret} 8/8 finished "
            f"in {steps} steps, tokens == reference_generate, decode "
            f"buckets (batch, pages)={buckets}")


# ---- cache -----------------------------------------------------------------
class CacheCount:
    """Entries on disk and the cache's answers in this process, by the
    program's own counters (``core/device.py`` listens to jax's events,
    ``runtime/aot.py`` counts its cache's)."""

    def __init__(self, directory):
        self.dir = directory
        self.start = len(os.listdir(directory))

    def report(self):
        from paddle_tpu import obs
        from paddle_tpu.runtime import aot

        st = aot.cache_stats()     # stores and rejects have no counter
        say(f"[cache] dir={self.dir} entries_at_start={self.start} "
            f"entries_at_exit={len(os.listdir(self.dir))} "
            f"jax_cache_hits={obs.counter('jax.cache.hits').value} "
            f"jax_cache_misses={obs.counter('jax.cache.misses').value} "
            f"aot_hits={obs.counter('aot.cache.hits').value} "
            f"aot_stores={st['stores']} aot_rejects={st['rejects']}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        if PLATFORM != "cpu":
            sys.exit(f"--rehearse-cpu is for the cpu backend; jax found "
                     f"platform={PLATFORM}")
        say("REHEARSAL: toy shapes, Pallas interpreter; proves nothing "
            "about the chip")
    elif PLATFORM != "tpu":
        sys.exit(f"chip_smoke needs a TPU: jax found platform={PLATFORM} "
                 f"({jax.devices()[0].device_kind} x{len(jax.devices())})")
    size = REHEARSAL if args.rehearse_cpu else CHIP

    import paddle_tpu as pt
    from paddle_tpu.ops import pallas as pk

    if args.rehearse_cpu:
        global KERNELS
        KERNELS = True  # route the call sites as a TPU backend does
        pk.set_enabled(KERNELS)
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.xla_cache
    cache = CacheCount(pt.set_compilation_cache())
    phase_device(args.devices, args.rehearse_cpu)
    if args.devices == 1:
        phase_kernels(size, interpret=args.rehearse_cpu)
        phase_train(size)
        phase_plain_mtp()
        phase_hybrid()
        phase_laguna()
        phase_ssm_hybrid(args.rehearse_cpu)
        phase_serve(size)
    else:
        phase_train_sharded(size, *phase_train(size))
    cache.report()
    if args.rehearse_cpu:
        say("REHEARSAL finished: every phase ran; no result is reported")
        return
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
