"""Benchmark: training and serving legs on one real TPU chip.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "platform": "tpu",
   "device_kind": ..., "device_count": N, ...extras}

Workloads (the 5 BASELINE.json configs + two serving extras):
  - BERT-Base pretrain step, seq 128 (headline: tokens/sec/chip)
  - ResNet-50 train step (imgs/sec/chip)
  - GPT-2-small train step, seq 1024 (tokens/sec/chip + MFU)
  - Transformer-base WMT beam-4 inference (single-executable
    lax.while_loop decode; output tokens/sec + per-sentence latency)
  - MNIST LeNet static Program/Executor train step (imgs/sec incl.
    host feed/fetch — the static-path overhead measurement)
  - LeNet int8-bundle Predictor serving (imgs/sec int8 vs fp32 +
    max prob diff -> int8_imgs_per_sec / int8_vs_fp32 extras)
  - TinyLM continuous-batching serve trace (tools/serve_bench.py)

All train legs run the fused donated TrainStep (fwd+bwd+clip+update in
one XLA executable), bf16 params with f32 master weights.

Every number printed here comes from the accelerator: with no TPU the
script exits non-zero before any leg, and a leg that raises ends the run
with its traceback and a non-zero exit. There is no CPU mode, no retry
with kernels off and no substitute peak. What the legs measure is
ROADMAP S1's to redesign; this file only guarantees where the numbers
came from. One process: it holds the chip for the whole run.
"""
import json
import os
import sys
import time

import numpy as np

RESNET50_TRAIN_FLOPS_PER_IMG = 12.3e9  # 3x the 4.1 GFLOP forward at 224x224

DEVICE = {}  # platform / device_kind / device_count, set once by main()


def _log(msg):
    tag = "[{platform} {device_kind} x{device_count}] ".format(**DEVICE) \
        if DEVICE else ""
    print(tag + msg, file=sys.stderr, flush=True)


def _peak_flops():
    from paddle_tpu.obs.mfu import peak_flops

    return peak_flops(DEVICE["device_kind"])


def _mfu(n_params, n_layers, hidden, B, L, dt):
    """Model FLOPs utilization; denominator includes attention FLOPs
    (PaLM appendix B formula: 6N + 12*n_layer*d_model*L per token)."""
    flops_per_token = 6.0 * n_params + 12.0 * n_layers * hidden * L
    return flops_per_token * B * L / dt / _peak_flops()


def _time_step(step, batch, warmup=3, iters=10):
    import jax

    for _ in range(warmup):
        loss = step(*batch)
    jax.block_until_ready(loss._data)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(*batch)
    jax.block_until_ready(loss._data)
    return (time.perf_counter() - t0) / iters, float(np.asarray(loss._data))


def _step_collectives(step, leg):
    """CollectiveProfile of a timed train step (obs.spmd), as one
    structured stderr JSON line + a compact dict for the bench extras.
    Single-chip legs honestly report zero collectives."""
    prof = step.collective_profile()
    if prof is None:
        return None
    _log("COLLECTIVE_PROFILE " + json.dumps(
        {"leg": leg, **prof}, sort_keys=True))
    return {"n_ops": prof["n_ops"], "counts": prof["counts"],
            "total_bytes": prof["total_bytes"],
            "wire_bytes": prof["wire_bytes"]}


def bench_bert(B=64, L=128):
    import paddle_tpu as pt
    from paddle_tpu import optim
    from paddle_tpu.models.nlp.bert import (BertForPretraining, bert_base,
                                            bert_pretrain_loss)

    pt.seed(0)
    cfg = bert_base()
    model = BertForPretraining(cfg)
    model.bfloat16()
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=1e-4,
                      multi_precision=True,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step = pt.TrainStep(model, opt, bert_pretrain_loss)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, L)).astype("int32")
    tt = np.zeros((B, L), "int32")
    am = np.ones((B, L), "int32")
    mlm = np.where(rng.rand(B, L) < 0.15, ids, -100).astype("int32")
    nsp = rng.randint(0, 2, (B,)).astype("int32")
    dt, loss = _time_step(step, (ids, tt, am, mlm, nsp))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tokens_s = B * L / dt
    mfu = _mfu(n_params, cfg.layers, cfg.hidden, B, L, dt)
    return {"tokens_per_sec": tokens_s, "step_ms": dt * 1e3, "mfu": mfu,
            "loss": loss, "params": n_params,
            "collectives": _step_collectives(step, "bert")}


def bench_resnet50(B=128, size=224):
    import paddle_tpu as pt
    from paddle_tpu import optim
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.vision import resnet50

    pt.seed(0)
    model = resnet50()
    model.bfloat16()
    opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=model.parameters(),
                         multi_precision=True)
    step = pt.TrainStep(
        model, opt,
        lambda m, x, y: F.cross_entropy(
            m(x.astype("bfloat16")).astype("float32"), y))
    rng = np.random.RandomState(0)
    x = rng.randn(B, 3, size, size).astype(np.float32)
    y = rng.randint(0, 1000, (B,)).astype("int32")
    dt, loss = _time_step(step, (x, y))
    # the 12.3 GFLOP/img constant is a 224x224 figure: scale for other
    # probe sizes (conv FLOPs go with spatial area)
    flops_img = RESNET50_TRAIN_FLOPS_PER_IMG * (size / 224.0) ** 2
    mfu = flops_img * B / dt / _peak_flops()
    return {"imgs_per_sec": B / dt, "step_ms": dt * 1e3, "mfu": mfu,
            "loss": loss,
            "collectives": _step_collectives(step, "resnet50")}


def bench_gpt(B=16, L=1024):
    import paddle_tpu as pt
    from paddle_tpu import optim
    from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss

    pt.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden=768, layers=12, heads=12,
                    max_seq=L, dropout=0.0)
    model = GPT(cfg)
    model.bfloat16()
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=1e-4,
                      multi_precision=True,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step = pt.TrainStep(model, opt, gpt_loss)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, L)).astype("int32")
    labels = np.roll(ids, -1, axis=1).astype("int32")
    dt, loss = _time_step(step, (ids, labels))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tokens_s = B * L / dt
    mfu = _mfu(n_params, cfg.layers, cfg.hidden, B, L, dt)
    return {"tokens_per_sec": tokens_s, "step_ms": dt * 1e3, "mfu": mfu,
            "loss": loss, "params": n_params,
            "collectives": _step_collectives(step, "gpt")}


def bench_wmt_beam(B=16, L_src=32, beam=4, max_len=32):
    """Transformer-base WMT en-de beam-search inference through the
    single-executable decode (encode + static-KV-cache lax.while_loop
    beam in ONE XLA program — no per-token host sync)."""
    import paddle_tpu as pt
    from paddle_tpu.models.nlp.transformer import WMTTransformer

    pt.seed(0)
    model = WMTTransformer(32000, 32000, d_model=512, nhead=8,
                           num_layers=6, dim_feedforward=2048,
                           dropout=0.0, max_len=max_len)
    model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    src = rng.randint(2, 32000, (B, L_src)).astype("int64")
    warmup, iters = 2, 8
    import jax

    for _ in range(warmup):
        toks, _ = model.beam_search_decode_xla(src, beam_size=beam,
                                               max_len=max_len)
    jax.block_until_ready(toks._data)
    t0 = time.perf_counter()
    for _ in range(iters):
        toks, _ = model.beam_search_decode_xla(src, beam_size=beam,
                                               max_len=max_len)
    jax.block_until_ready(toks._data)
    dt = (time.perf_counter() - t0) / iters
    return {"tokens_per_sec": B * max_len / dt,
            "sentences_per_sec": B / dt,
            "latency_ms_per_batch": dt * 1e3, "beam": beam}


def bench_int8_predictor(B=256):
    """LeNet served via the int8 bundle (save -> quantize_inference_model
    -> Predictor): imgs/sec int8 vs fp32 through the same Predictor path.
    The int8 copy is HBM-resident with the dequant fused into the
    consumer — on small models this measures dispatch + weight-traffic,
    the serving overhead axis."""
    import tempfile

    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.inference import Predictor
    from paddle_tpu.models.vision import LeNet
    from paddle_tpu.quant import quantize_inference_model

    pt.seed(0)
    pt.enable_static()
    try:
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            xv = pt.static.data("x", [B, 1, 28, 28], "float32")
            prob = F.softmax(LeNet()(xv), axis=-1)
    finally:
        pt.disable_static()
    exe = pt.static.Executor()
    exe.run(startup)
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "lenet")
        pt.framework.io.save_inference_model(prefix, ["x"], [prob],
                                             program=main)
        quantize_inference_model(prefix)
        p32 = Predictor(prefix)
        p8 = Predictor(prefix + "_int8")
        x = np.random.RandomState(0).randn(B, 1, 28, 28).astype("float32")
        warmup, iters = 3, 20

        def rate(pred):
            for _ in range(warmup):
                pred.run({"x": x})
            t0 = time.perf_counter()
            for _ in range(iters):
                out, = pred.run({"x": x})
            return B / ((time.perf_counter() - t0) / iters), out

        r32, o32 = rate(p32)
        r8, o8 = rate(p8)
        return {"imgs_per_sec_int8": r8, "imgs_per_sec_fp32": r32,
                "int8_vs_fp32": r8 / r32 if r32 else 0.0,
                "max_prob_diff": float(np.abs(o32 - o8).max())}


# ceilings for the serve leg's exit-time SLO evaluation: generous for
# the dispatch-bound TinyLM, tight enough that a pathological
# scheduler/latency regression lands as a nonempty serve_slo_violations
# list in the one-line JSON
SERVE_SLO_SPEC = {"ttft_p99_ms": 30000.0, "tpot_p99_ms": 5000.0,
                  "availability": 0.9, "goodput_tps": 0.01}


def bench_serve(requests=48, rate=100.0, pages=256, page_size=16):
    """Continuous-batching serving (paddle_tpu.serving): a Poisson
    trace of mixed-length prompts through ServeEngine's paged-KV
    decode path, reporting tokens/s and p50/p99 TTFT/TPOT — the
    serving-latency axis the train legs can't see. The TinyLM is
    dispatch-bound by design: this measures the scheduler + paged
    decode step overhead, which is exactly what continuous batching
    amortizes."""
    import importlib.util
    import shutil
    import tempfile
    import urllib.request

    from paddle_tpu.obs import export as _export
    from paddle_tpu.obs import journal as _jl
    from paddle_tpu.obs.slo import evaluate_run
    from paddle_tpu.runtime import aot as _aot
    from paddle_tpu.serving.engine import ServeEngine, TinyLM
    from paddle_tpu.serving.kv_cache import PagedKVCache

    spec = importlib.util.spec_from_file_location(
        "serve_bench_leg",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    # weighted two-tenant trace: rates proportional to weights (3:1), so
    # the measured served-token share should track the weight share and
    # tenant_share_err stays a near-zero fairness canary — a
    # scheduler/fairness regression shows up as drift here before it
    # trips any latency gate
    tenants = sb.parse_tenants(
        f"a:rate={0.75 * rate:g},weight=3;b:rate={0.25 * rate:g},weight=1")
    # journal the leg so SERVE_SLO_SPEC evaluates post-hoc over the real
    # per-request records — the same obs.slo.evaluate_run math
    # ``serve_bench --slo`` gates on
    slo_dir = tempfile.mkdtemp(prefix="pt_serve_slo_")
    try:
        _jl.start_run(slo_dir)
        try:
            rep = sb.run_bench(n_requests=requests, rate=rate, pages=pages,
                               page_size=page_size, tenants=tenants)
        finally:
            _jl.end_run()
        slo_rep = evaluate_run(slo_dir, SERVE_SLO_SPEC,
                               duration_s=rep["wall_s"])
    finally:
        shutil.rmtree(slo_dir, ignore_errors=True)
    out = {
        "tokens_per_sec": rep["tokens_per_sec"],
        "ttft_p50_ms": rep["ttft_p50_ms"],
        "ttft_p99_ms": rep["ttft_p99_ms"],
        "tpot_p50_ms": rep["tpot_p50_ms"],
        "tpot_p99_ms": rep["tpot_p99_ms"],
        "requests": rep["requests"], "finished": rep["finished"],
        "preemptions": rep["preemptions"],
        "kv_fragmentation": rep["kv_fragmentation"],
        "tenant_share_err": rep.get("tenant_share_err"),
        "slo_violations": slo_rep["violations"],
    }
    # replica cold-start vs warm-start: time-to-first-request of a
    # fresh ServeEngine against a fresh AOT executable cache (compiles
    # prefill + decode buckets) vs the same cache warm (hydrates) —
    # the autoscaling-speed axis the throughput numbers can't see. A
    # per-engine directory of its own, never the process-wide cache.
    tmpd = tempfile.mkdtemp(prefix="pt_aot_serve_")

    def first_request_ms():
        model = TinyLM(vocab_size=32, num_heads=2, head_dim=8, seed=0)
        kv = PagedKVCache(32, 4, 2, 8, max_seq_len=32)
        eng = ServeEngine(model, kv, aot_cache_dir=tmpd)
        t0 = time.perf_counter()
        eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
        eng.run()
        return (time.perf_counter() - t0) * 1e3

    try:
        cold = first_request_ms()
        warm = first_request_ms()
        out.update({
            "cold_start_ms": cold, "warm_start_ms": warm,
            "aot_hits": _aot.resolve_cache(tmpd).stats()["hits"]})
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)
    # live SLO exporter scrape MID-RUN: an engine with requests still
    # in flight, scraped once over real localhost HTTP — the
    # autoscaler-signal-plane latency axis (obs.export) plus a sanity
    # check that the scraped running-count gauge matches the engine
    eng = ServeEngine(TinyLM(vocab_size=32, num_heads=2, head_dim=8,
                             seed=0),
                      PagedKVCache(32, 4, 2, 8, max_seq_len=32))
    for prompt in ([3, 1, 4], [1, 5], [9]):
        eng.submit(prompt, max_new_tokens=6)
    eng.run(max_steps=2)  # mid-run: decodes still in flight
    expected_running = float(len(eng.scheduler.running))
    exp = _export.MetricsExporter(engines=[eng])
    port = exp.start()
    try:
        t0 = time.perf_counter()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            body = resp.read().decode("utf-8")
        scrape_ms = (time.perf_counter() - t0) * 1e3
    finally:
        exp.stop()
    vals = _export.parse_prometheus_text(body)
    running = vals.get(f'paddle_tpu_serving_slo_running'
                       f'{{replica="{eng.replica_id}"}}')
    eng.run()  # drain
    out.update({
        "export_scrape_ms": scrape_ms,
        "export_gauge_ok": bool(
            running is not None and running == expected_running
            and expected_running >= 1.0)})
    # multi-replica router leg: the same Poisson trace class through a
    # 2-replica serving.fleet Router (in-process replicas) — the
    # dispatch-layer tax (router_overhead_ms) and fleet-aggregate
    # latency axes next to the single-engine numbers
    rep2 = sb.run_bench_fleet(
        n_requests=min(requests, 24), rate=rate, replicas=2,
        pages=pages, page_size=page_size, tenants=tenants)
    out.update({
        "replicas": rep2["replicas"],
        "router_overhead_ms": rep2["router_overhead_ms"],
        "fleet_tokens_per_sec": rep2["tokens_per_sec"],
        "fleet_ttft_p99_ms": rep2["ttft_p99_ms"],
        "fleet_requeued": rep2["requeued"],
        "fleet_tenant_share_err": rep2.get("tenant_share_err"),
    })
    return out


def bench_lenet_exec(B=256, K=8):
    """MNIST LeNet through the static Program/Executor feed/fetch loop
    (BASELINE config 1) — measures compiled-program dispatch + host
    round-trip overhead, the role the fluid Executor played. Also times
    the fused multi-step path (K microbatches per lax.scan dispatch,
    ``Executor.run_steps``) and reports the compiled-call accounting
    (compiles + dispatches) for both."""
    import paddle_tpu as pt
    from paddle_tpu import optim
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.vision import LeNet

    pt.seed(0)
    rng = np.random.RandomState(0)
    x = rng.randn(B, 1, 28, 28).astype("float32")
    y = rng.randint(0, 10, (B,)).astype("int64")
    pt.enable_static()
    try:
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            xv = pt.static.data("x", [B, 1, 28, 28], "float32")
            yv = pt.static.data("y", [B], "int64")
            model = LeNet()
            loss = F.cross_entropy(model(xv), yv)
            optim.Momentum(0.01, 0.9,
                           parameters=model.parameters()).minimize(loss)
    finally:
        pt.disable_static()
    exe = pt.static.Executor()
    exe.run(startup)
    warmup, iters = 3, 20
    for _ in range(warmup):
        exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss])
    dt = (time.perf_counter() - t0) / iters
    res = {"imgs_per_sec": B / dt, "step_ms": dt * 1e3,
           "loss": float(np.asarray(out[0]))}
    # fused path: same program, K microbatches per compiled dispatch
    feeds = [{"x": x, "y": y}] * K
    exe.run_steps(main, feeds=feeds, fetch_list=[loss])  # warm/compile
    t0 = time.perf_counter()
    for _ in range(max(1, iters // K)):
        fused_out = exe.run_steps(main, feeds=feeds, fetch_list=[loss])
    fdt = (time.perf_counter() - t0) / max(1, iters // K)
    res.update({
        "fused_imgs_per_sec": B * K / fdt,
        "fused_step_ms": fdt / K * 1e3,
        "steps_fused": K,
        "fused_vs_loop": (B * K / fdt) / (B / dt) if dt else 0.0,
        "fused_loss": float(np.asarray(fused_out[0][-1])),
    })
    cs = exe.cache_stats()
    res["compiled_calls"] = {"compiles": cs["misses"],
                             "dispatches": exe.dispatches,
                             "entries": cs["size"]}
    return res


def _require_tpu():
    """The accelerator, or a non-zero exit before any leg runs: a number
    from another backend must never appear under these metric names."""
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench.py needs a TPU: jax found platform="
                 f"{jax.default_backend()!r} ({jax.devices()[0].device_kind}"
                 f" x{len(jax.devices())}); nothing was measured")
    from paddle_tpu.core.device import device_identity

    DEVICE.update(device_identity())
    _peak_flops()  # unknown device_kind is an error now, not after an hour


LEGS = (("bert", bench_bert), ("resnet50", bench_resnet50),
        ("gpt", bench_gpt), ("wmt_beam", bench_wmt_beam),
        ("lenet_exec", bench_lenet_exec),
        ("int8_predictor", bench_int8_predictor), ("serve", bench_serve))


def main():
    _require_tpu()
    from paddle_tpu import set_compilation_cache

    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.xla_cache
    _log(f"compile cache: {set_compilation_cache()}")
    results = {}
    for name, fn in LEGS:   # a leg that raises ends the run, traceback out
        t0 = time.perf_counter()
        results[name] = fn()
        _log(f"{name}: {results[name]} "
             f"({time.perf_counter() - t0:.0f}s incl. compile)")
    print(json.dumps({**_score(results), **DEVICE}), flush=True)


def _score(results):
    extras = {}
    # structured collective accounting per train leg (obs.spmd): rides
    # the one-line JSON so records carry comm volumes, not prose
    coll = {leg: results[leg]["collectives"]
            for leg in ("bert", "resnet50", "gpt")
            if results[leg].get("collectives")}
    if coll:
        extras["collectives"] = coll
    headline = {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(results["bert"]["tokens_per_sec"], 1),
        "unit": "tokens/s",
    }
    extras["bert_mfu"] = round(results["bert"]["mfu"], 4)
    extras["resnet50_imgs_per_sec"] = round(
        results["resnet50"]["imgs_per_sec"], 1)
    extras["resnet50_mfu"] = round(results["resnet50"]["mfu"], 4)
    extras["gpt_tokens_per_sec"] = round(
        results["gpt"]["tokens_per_sec"], 1)
    extras["gpt_mfu"] = round(results["gpt"]["mfu"], 4)
    extras["wmt_beam_tokens_per_sec"] = round(
        results["wmt_beam"]["tokens_per_sec"], 1)
    extras["wmt_beam_latency_ms"] = round(
        results["wmt_beam"]["latency_ms_per_batch"], 1)
    le = results["lenet_exec"]
    extras["lenet_exec_imgs_per_sec"] = round(le["imgs_per_sec"], 1)
    extras["lenet_fused_imgs_per_sec"] = round(le["fused_imgs_per_sec"], 1)
    extras["lenet_fused_vs_loop"] = round(le["fused_vs_loop"], 3)
    extras["steps_fused"] = le["steps_fused"]
    extras["compiled_calls"] = le["compiled_calls"]
    extras["int8_imgs_per_sec"] = round(
        results["int8_predictor"]["imgs_per_sec_int8"], 1)
    extras["int8_vs_fp32"] = round(
        results["int8_predictor"]["int8_vs_fp32"], 3)
    extras["int8_max_prob_diff"] = round(
        results["int8_predictor"]["max_prob_diff"], 5)
    sv = results["serve"]
    extras["serve_tokens_per_sec"] = round(sv["tokens_per_sec"] or 0.0, 1)
    for k in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms"):
        if sv.get(k) is not None:
            extras["serve_" + k] = round(sv[k], 2)
    extras["serve_preemptions"] = sv["preemptions"]
    if sv.get("tenant_share_err") is not None:
        # max |served-token share - weight share| over the leg's
        # weighted two-tenant trace
        extras["serve_tenant_share_err"] = round(sv["tenant_share_err"], 4)
    extras["export_scrape_ms"] = round(sv["export_scrape_ms"], 2)
    extras["export_gauge_ok"] = sv["export_gauge_ok"]
    extras["serve_cold_start_ms"] = round(sv["cold_start_ms"], 1)
    extras["serve_warm_start_ms"] = round(sv["warm_start_ms"], 1)
    extras["aot_hits"] = sv["aot_hits"]
    extras["serve_slo_violations"] = sv["slo_violations"]
    extras["serve_slo_ok"] = not sv["slo_violations"]
    extras["serve_replicas"] = sv["replicas"]
    extras["serve_router_overhead_ms"] = round(sv["router_overhead_ms"], 2)
    if sv.get("fleet_ttft_p99_ms") is not None:
        extras["serve_fleet_ttft_p99_ms"] = round(
            sv["fleet_ttft_p99_ms"], 2)
    if sv.get("fleet_tenant_share_err") is not None:
        extras["serve_fleet_tenant_share_err"] = round(
            sv["fleet_tenant_share_err"], 4)
    return {**headline, **extras}


if __name__ == "__main__":
    main()
