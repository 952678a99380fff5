"""The training loop kind: a closed loop two steps deep, as a training job
that logs its loss asynchronously.

Set-up builds ONE step object, drives it from the seed through its first
three steps (read for ``correct``) and a warm-up, and hands that same object
to the window. Every step, checked, warm-up or timed, goes through
``Loop.one_step``: next batch from ``paddle_tpu.io_.DataLoader``, dispatch,
then wait for the step before.
"""
import contextlib
import dataclasses
import gc
import math
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import correct, generate, harness
from benchmark.reference import _common as ref_common

CHECKED_STEPS = 3
WARMUP_STEPS = 4
TRACE_STEPS = 6            # steady steps a --trace 1 run profiles after the window
REFERENCE_MICRO_ROWS = 2       # the reference sums its batch two rows at a
REFERENCE_MICRO_TOKENS = 4096  # time, or these tokens where that is fewer
SMOOTH_SPAN_MS = 250.0     # step_ms_p90_smooth reads spans of this or more
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCount:
    """Backend compiles (or cache loads) the process asks for, by
    jax.monitoring, as chip_smoke.py:CacheCount counts cache events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        self.n += event == COMPILE_EVENT


class Pool:
    """The in-memory rows as a map-style dataset (one row a sample)."""

    def __init__(self, arrays):
        self.arrays = arrays

    def __getitem__(self, i):
        return tuple(a[i] for a in self.arrays)

    def __len__(self):
        return len(self.arrays[0])


class Loop:
    def __init__(self, step, loader, tokens_per_batch):
        self.step, self.loader = step, loader
        self.tokens_per_batch = tokens_per_batch
        self.feed = self._endless()
        self.n_fed = 0
        self.pending = None
        self.spans = []    # (name, start, end) on time.perf_counter
        self.losses = []   # device scalars of completed steps
        self.annotate = contextlib.nullcontext  # or TraceAnnotation

    def _endless(self):
        while True:
            yield from self.loader

    def one_step(self):
        """Feed and dispatch one step, then wait for the one before it.
        Returns the host time at which that earlier step was seen complete
        (None for the first call after a drain)."""
        t0 = time.perf_counter()
        with self.annotate("bench.data_wait"):
            batch = next(self.feed)
        t1 = time.perf_counter()
        with self.annotate("bench.dispatch"):
            loss = self.step(*batch)
        t2 = time.perf_counter()
        self.n_fed += 1
        done = None
        if self.pending is not None:
            with self.annotate("bench.block"):
                done = self._wait()
        self.pending = loss
        self.spans += [("data_wait", t0, t1), ("dispatch", t1, t2)] + \
            ([("block", t2, done)] if done else [])
        return done

    def _wait(self):
        jax.block_until_ready(self.pending._data)
        self.losses.append(self.pending._data)
        self.pending = None
        return time.perf_counter()

    def drain(self):
        return self._wait() if self.pending is not None else None

    def tokens(self, first, count):
        """Non-padding tokens of batches first .. first + count - 1."""
        per = self.tokens_per_batch
        return int(sum(per[i % len(per)] for i in range(first, first + count)))


def _leaf_norms(arrays):
    return [float(x) for x in jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs
    ])(arrays)]


def _delta_norms(now, start):
    return [float(x) for x in jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) -
                                    y.astype(jnp.float32))))
        for x, y in zip(a, b)])(now, start)]


def _samples(arrays, index, scale):
    return [np.asarray(x) for x in jax.jit(lambda xs: [
        x.reshape(-1)[i] * scale for x, i in zip(xs, index)])(arrays)]


def program_readings(loop, model, optimizer, names, weights, index, beta1):
    """The program's side of ``correct``: three steps through the loop's own
    call, one at a time so that the optimizer's state can be read between
    them. The state is the program's (``optimizer._accumulators``: moment1
    and the float32 master copy); the arithmetic on it is ours."""
    params = dict(model.named_parameters())
    losses, grad_norms, first_step_s = [], None, None
    for i in range(CHECKED_STEPS):
        t = time.perf_counter()
        loop.one_step()
        done = loop.drain()
        first_step_s = first_step_s or done - t
        losses.append(float(loop.losses[-1]))
        slots = [optimizer._accumulators[params[n].name] for n in names]
        if i == 0:
            moments = [s["moment1"] for s in slots]
            grad_norms = [x / (1.0 - beta1) for x in _leaf_norms(moments)]
            grad_sample = _samples(moments, [index[names[n]] for n in names],
                                   1.0 / (1.0 - beta1))
            del moments
    delta = _delta_norms([s["master"] for s in slots],
                         [weights[names[n]] for n in names])
    ref_names = [names[n] for n in names]
    return {"losses": losses, "first_step_s": first_step_s,
            "grad_norms": dict(zip(ref_names, grad_norms)),
            "grad_sample": dict(zip(ref_names, grad_sample)),
            "delta_norms": dict(zip(ref_names, delta))}


def micro_rows(row_tokens):
    """Rows of ``row_tokens`` tokens to a micro-batch of the reference: two,
    or what 4,096 tokens hold where that is fewer, never under one."""
    return max(1, min(REFERENCE_MICRO_ROWS,
                      REFERENCE_MICRO_TOKENS // row_tokens))


def reference_readings(family, cell, weights, batches, index, precision):
    """``weights`` comes back empty (``train_steps`` takes the leaves one by
    one); the micro-batch follows the batch's own row length."""
    cfg = cell["config"]
    return ref_common.train_steps(
        family.reference.loss_part(cfg), family.reference.denominators,
        weights, batches, cfg["recipe"], index,
        micro=micro_rows(batches[0][0].shape[1]), precision=precision,
        devices=jax.devices()[:cell["chips"]])


def _check_placement(cell, model, compiled_text, batch_shape):
    """A mesh cell's parameters live on every chip of the mesh and each chip
    computes its share of the batch: asserted, not only printed."""
    chips = cell["chips"]
    for name, p in model.named_parameters():
        if len(p._data.sharding.device_set) != chips:
            raise AssertionError(f"{name} lives on "
                                 f"{len(p._data.sharding.device_set)} "
                                 f"devices, not {chips}")
    share = cell["mesh"].get("data", 1)
    rows, length = batch_shape
    local = f"s32[{rows // share},{length}]"
    if share > 1 and (local not in compiled_text or
                      f"s32[{rows},{length}]" in compiled_text):
        raise AssertionError(f"the compiled step does not hold a {local} "
                             f"share of the batch per device")


def bytes_in_use():
    """Live bytes on the fullest device, by the runtime's own count (None
    where the backend keeps none)."""
    return max(((d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()), key=lambda b: b or 0)


def period_p90(stamps, k=1):
    """90th percentile of the step period in ms: the interval between
    consecutive completions, or with ``k`` > 1 the mean of ``k`` of them, one
    span starting at every completion."""
    periods = [(b - a) / k * 1e3 for a, b in zip(stamps, stamps[k:])]
    return float(np.percentile(periods, 90))


def smooth_steps(stamps):
    """Steps to a span of SMOOTH_SPAN_MS or more, from the window's own
    median period (and no more than the window has)."""
    median = statistics.median(b - a for a, b in zip(stamps, stamps[1:]))
    return max(1, min(math.ceil(SMOOTH_SPAN_MS / (1e3 * median)),
                      len(stamps) - 1))


class GcSpans:
    """The interpreter's garbage collections as host spans beside the loop's
    own, so that a stall in the window names its cause."""

    def __init__(self, spans):
        self.spans, self.began = spans, None

    def __call__(self, phase, info):
        if phase == "start":
            self.began = time.perf_counter()
        elif self.began is not None:
            self.spans.append((f"gc{info['generation']}", self.began,
                               time.perf_counter()))
            self.began = None


def timed_window(loop, seconds, compiles):
    """The measured window: from a completed step to ``block_until_ready`` on
    the last step's loss, ``seconds`` long to within a step."""
    del loop.spans[:], loop.losses[:]
    watch = GcSpans(loop.spans)
    gc.callbacks.append(watch)
    start = loop.drain()
    first_batch, compiles_before = loop.n_fed, compiles.n
    stamps = [start]
    while stamps[-1] - start < seconds:
        done = loop.one_step()
        if done is not None:
            stamps.append(done)
    stamps.append(loop.drain())
    compiled_inside = compiles.n - compiles_before   # before any jnp of ours
    gc.callbacks.remove(watch)
    steps = loop.n_fed - first_batch
    longest = sorted(loop.spans, key=lambda x: x[1] - x[2])[:3]
    collections = [e - b for n, b, e in loop.spans if n.startswith("gc")]
    return {
        "start": start, "seconds": stamps[-1] - start, "steps": steps,
        "stamps": stamps, "tokens": loop.tokens(first_batch, steps),
        "failed": int(np.sum(~np.isfinite(np.asarray(jnp.stack(loop.losses))))),
        "compiles": compiled_inside,
        "host": f"{len(collections)} collections "
                f"{1e3 * sum(collections):.1f} ms in all; longest spans "
                + ", ".join(f"{n} {1e3 * (e - b):.0f} ms at {b - start:.1f}s"
                            for n, b, e in longest)}


@dataclasses.dataclass
class Setup:
    family: object
    weights: dict    # reference name -> seeded bfloat16 array
    pool: tuple
    model: object
    step: object
    loop: Loop
    names: dict      # program's structured name -> reference name
    index: dict      # reference name -> sampled entries (sample_index)

    def first_batches(self, n, batch):
        return [tuple(a[i * batch:(i + 1) * batch] for a in self.pool)
                for i in range(n)]


def set_up(cell, seed):
    """Weights and rows from the seed, the program's model and ONE step
    object through its normal path, and the loop that feeds it."""
    from paddle_tpu import io_

    cfg, traffic = cell["config"], cell["traffic"]
    family = harness.load_module("families", cfg["family"])
    specs = family.reference.param_specs(cfg)
    weights = ref_common.init_weights(specs, seed)
    pool = generate.pool(traffic, cfg["vocab_size"], seed)
    batch = traffic["batch"]
    tokens_per_batch = family.valid_tokens(pool).reshape(-1, batch).sum(axis=1)
    model, step = family.build(cfg, weights, cell.get("mesh"))
    loader = io_.DataLoader(Pool(pool), batch_size=batch)
    return Setup(family, weights, pool, model, step,
                 Loop(step, loader, tokens_per_batch), family.name_map(cfg),
                 ref_common.sample_index(specs))


def run(cell, args, t_start, say, read_layers):
    """One run of a training cell; returns the harness's result parts.
    ``read_layers(window)`` gives the per-layer metrics of a traced run; it is
    called while the program's step is still there to be read."""
    cfg, traffic = cell["config"], cell["traffic"]
    compiles = CompileCount()
    t = time.perf_counter()
    say(f"[setup] imports and device in {t - t_start:.1f}s")
    su = set_up(cell, args.seed)
    say(f"[setup] weights, rows, model and step in "
        f"{time.perf_counter() - t:.1f}s")
    family, model, step, loop, index = \
        su.family, su.model, su.step, su.loop, su.index
    batch = traffic["batch"]

    t = time.perf_counter()
    got = program_readings(loop, model, step.optimizer, su.names, su.weights,
                           index, cfg["recipe"]["beta1"])
    first_step_s = got.pop("first_step_s")
    say(f"[setup] three checked steps in {time.perf_counter() - t:.1f}s, "
        f"first {first_step_s:.1f}s, losses {got['losses']}")
    held = bytes_in_use()
    su.weights = None   # a pure function of the seed: made again for the reference
    say(f"[setup] bytes_in_use {held} with the seeded weights, "
        f"{bytes_in_use()} without them, as the window runs")
    for _ in range(WARMUP_STEPS):
        loop.one_step()

    win = timed_window(loop, args.seconds, compiles)
    setup_s = win["start"] - t_start
    attempted, failed, window_s = win["steps"], win["failed"], win["seconds"]
    compiles_in_window = win["compiles"]
    metrics = {
        "tokens_per_s_per_chip": win["tokens"] / window_s / cell["chips"],
        "step_ms_p90": period_p90(win["stamps"]),
        "setup_s": setup_s,
    }
    k = smooth_steps(win["stamps"])
    say(f"[window] {attempted} steps in {window_s:.3f}s, {win['tokens']} "
        f"tokens, step period p90 {metrics['step_ms_p90']:.3f} ms "
        f"({period_p90(win['stamps'], k):.3f} over {k}-step spans), "
        f"compiles_in_window={compiles_in_window}; host: " + win["host"])

    compiled = step.compiled()
    text = compiled.as_text()
    window = harness.Window(
        cell=cell, family=family, compiled=compiled,
        compiled_text=text, spans=list(loop.spans), steps=attempted,
        stamps=win["stamps"], seconds=window_s, first_step_s=first_step_s,
        compiles_in_window=compiles_in_window)
    if cell.get("mesh"):
        _check_placement(cell, model, text, (batch, traffic["seq_len"]))
    layers = breakdown = None
    if args.trace:
        window.trace = harness.traced_steps(loop, TRACE_STEPS, say)
        layers, breakdown = read_layers(window), harness.breakdown(window.trace)
    device = harness.device_report(compiled, window.trace)

    # ---- correct: the reference runs once the program's state is freed ----
    batches = su.first_batches(CHECKED_STEPS, batch)
    del su, loop, step, model, compiled, window
    gc.collect()
    t = time.perf_counter()
    weights = ref_common.init_weights(family.reference.param_specs(cfg),
                                      args.seed)
    want = reference_readings(family, cell, weights, batches, index,
                              "float32")
    reference_s = time.perf_counter() - t
    say(f"[correct] reference followed {CHECKED_STEPS} steps in "
        f"{reference_s:.1f}s, losses {want['losses']}")
    numbers = correct.compare(got, want)
    numbers["compiles_in_window"] = (float(compiles_in_window), None)
    numbers["nonfinite_losses"] = (float(failed), None)
    limits = {**cell["limits"], "compiles_in_window": 0.0,
              "nonfinite_losses": 0.0}
    if cell.get("kernels"):
        # the kernel families the cell file says the step must hold, by the
        # program's own pallas_call names: a count of those with no call
        absent = harness.missing_kernels(text, cell["kernels"])
        numbers["missing_kernels"] = (float(len(absent)),
                                      ", ".join(absent) or None)
        limits["missing_kernels"] = 0.0
    ok = correct.judge(numbers, limits, say)
    return {"correct": ok, "attempted": attempted, "failed": failed,
            "end_to_end": metrics, "per_layer": layers,
            "breakdown": breakdown, "device": device,
            "reference_s": reference_s,
            "checks": {name: {"value": value, "limit": limits[name],
                              **({"where": where} if where else {})}
                       for name, (value, where) in numbers.items()}}
