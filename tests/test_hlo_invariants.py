"""Perf-critical invariants asserted on the compiled (post-optimization)
HLO text + XLA memory analysis — structure checks that run on the CPU
backend. They are counts, not speed (ROADMAP D9).

The reference enforces analogous properties with IR passes over its graph
(paddle/fluid/framework/ir/graph_pattern_detector.cc); here the invariants
are asserted directly on what XLA will execute:
  (a) the static-DP executable contains grad all-reduces, the
      single-device one doesn't;
  (b) donation really aliases: every donated persistable (static
      Executor) / every param+opt-state leaf (TrainStep) has an
      input_output_alias entry, so params are not double-buffered;
  (c) the fused beam search is ONE while-loop executable with zero host
      transfers;
  (d) the fused train step performs no full-size copy of optimizer
      moment buffers (scalar beta-pow copies are immaterial).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
import paddle_tpu.nn as nn
import paddle_tpu.optim as optim


def _build_mlp_program(lr=0.1, batch=16):
    prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[batch, 8])
        y = fluid.data(name="y", shape=[batch, 1])
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        opt = fluid.optimizer.SGD(learning_rate=lr)
        opt.minimize(loss)
    return prog, startup, loss


def _compiled_text(exe, prog, feed, fetch, data_parallel):
    """Optimized-HLO text of the Executor's cached executable for a feed."""
    from paddle_tpu.static_.program import global_scope

    compiled = exe._compile(prog, feed, fetch, data_parallel=data_parallel)
    scope = global_scope()
    feeds = [jnp.asarray(np.asarray(feed[n])) for n in compiled.feed_names]
    upd = [scope.find_var(n) for n in compiled.updated]
    frz = [scope.find_var(n) for n in compiled.frozen]
    lowered = compiled.fn.lower(feeds, upd, frz)
    return lowered.compile().as_text(), compiled


@pytest.fixture
def static_mode():
    pt.enable_static()
    yield
    pt.disable_static()


def _train_feed(prog):
    feed = {"x": np.zeros((16, 8), np.float32),
            "y": np.zeros((16, 1), np.float32)}
    if prog._lr_getter is not None:
        feed["@lr"] = np.asarray(prog._lr_getter(), np.float32)
    return feed


class TestStaticExecutorHLO:
    def test_dp_executable_has_allreduce_single_does_not(self, static_mode):
        pt.seed(0)
        prog, startup, loss = _build_mlp_program()
        exe = fluid.Executor()
        exe.run(startup)
        feed = _train_feed(prog)
        txt_dp, _ = _compiled_text(exe, prog, feed, [loss], True)
        txt_1, _ = _compiled_text(exe, prog, feed, [loss], False)
        assert "all-reduce" in txt_dp, "DP step lost its grad all-reduce"
        assert "all-reduce" not in txt_1

    def test_updated_persistables_are_aliased(self, static_mode):
        """donate_argnums=(1,) must alias EVERY updated persistable
        (params + opt slots) into the outputs — no double-buffering."""
        pt.seed(0)
        prog, startup, loss = _build_mlp_program()
        exe = fluid.Executor()
        exe.run(startup)
        feed = _train_feed(prog)
        txt, compiled = _compiled_text(exe, prog, feed, [loss], False)
        assert "input_output_alias" in txt
        n_updated = len(compiled.updated)
        assert n_updated >= 4  # 2xW, 2xb at minimum
        assert txt.count("alias") - txt.count("input_output_alias") \
            >= n_updated or txt.count("may-alias") >= n_updated, \
            f"expected >= {n_updated} alias entries"


class TestTrainStepHLO:
    def _compiled_step(self):
        from paddle_tpu.framework.jit import TrainStep
        from paddle_tpu.core import random as prandom

        m = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
        opt = optim.Adam(parameters=m.parameters(), learning_rate=1e-3)

        def loss_fn(model, x, y):
            d = model(x) - y
            return (d * d).mean()

        step = TrainStep(m, opt, loss_fn)
        x = np.zeros((16, 32), np.float32)
        y = np.zeros((16, 8), np.float32)
        step(x, y)
        fn = next(iter(step._compiled.values()))
        opt_state = {p.name: opt._accumulators[p.name]
                     for p in step._trainable}
        lowered = fn.lower([p._data for p in step._trainable],
                           [b._data for b in step._buffers], opt_state,
                           jnp.float32(1e-3), prandom.next_key(),
                           [jnp.asarray(x), jnp.asarray(y)], {})
        comp = lowered.compile()
        n_leaves = len(step._trainable) + len(step._buffers) + sum(
            len(v) for v in opt_state.values())
        return comp, n_leaves

    def test_all_params_and_state_aliased(self):
        comp, n_leaves = self._compiled_step()
        txt = comp.as_text()
        assert txt.count("may-alias") == n_leaves, \
            f"{txt.count('may-alias')} aliased of {n_leaves} donated leaves"
        ma = comp.memory_analysis()
        # aliased bytes must cover the params+state (less scalar slack):
        # if donation regressed, alias_size collapses and the step
        # double-buffers every parameter in HBM
        assert ma.alias_size_in_bytes >= 0.9 * ma.output_size_in_bytes

    def test_no_fullsize_copies_of_optimizer_state(self):
        comp, _ = self._compiled_step()
        txt = comp.as_text()
        bad = [ln for ln in txt.splitlines()
               if re.search(r"\w+\[\d[0-9,]*\]\S* copy\(\S*opt_state", ln)]
        assert not bad, "moment buffers copied instead of updated " \
            f"in place:\n" + "\n".join(bad[:5])


class TestFusedDecodeHLO:
    def test_beam_xla_single_while_no_host_transfers(self):
        from paddle_tpu.inference.decoder import beam_search_xla

        V, B, K, L = 11, 2, 3, 8

        def run(table):
            def step_fn(cur, state, t):
                logits = pt.Tensor(
                    jnp.tile(table, (cur.shape[0], 1)), _internal=True)
                return logits, state

            toks, scores = beam_search_xla(step_fn, None, B, bos_id=0,
                                           eos_id=1, beam_size=K, max_len=L)
            return toks._data, scores._data

        table = jnp.linspace(0.0, 1.0, V)
        txt = jax.jit(run).lower(table).compile().as_text()
        # op defs look like `%while.2 = (<tuple shape>) while(%tuple.N)`;
        # metadata op_names only ever contain "/while/" so ' while(' is
        # unambiguous
        n_while = txt.count(" while(")
        assert n_while == 1, f"expected ONE fused decode loop, got {n_while}"
        for marker in ("infeed", "outfeed", " send(", " recv(",
                       "SendToHost", "RecvFromHost"):
            assert marker not in txt, f"host transfer {marker!r} in decode"


class TestInt8PredictorHLO:
    def test_int8_weights_enter_executable_as_s8(self, tmp_path):
        """The int8 serving claim, proven on the compiled executable:
        quantized weights are s8[...] PARAMETERS of the HLO module (the
        resident HBM copy), and the convert to float happens inside the
        program (fused dequant), not on the host before the call."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.inference import Predictor
        from paddle_tpu.models.vision import LeNet
        from paddle_tpu.quant import quantize_inference_model

        pt.seed(0)
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.program_guard(main, startup):
                x = pt.static.data("x", [8, 1, 28, 28], "float32")
                prob = F.softmax(LeNet()(x), axis=-1)
        finally:
            pt.disable_static()
        exe = pt.static.Executor()
        exe.run(startup)
        prefix = str(tmp_path / "lenet")
        pt.framework.io.save_inference_model(prefix, ["x"], [prob],
                                             program=main)
        quantized = quantize_inference_model(prefix)
        assert quantized

        pred = Predictor(prefix + "_int8")
        xs = np.zeros((8, 1, 28, 28), np.float32)
        pred.run({"x": xs})  # compile
        # entries are _PredictorEntry since PR 7 (fn + captured
        # arg_structs, the perf-gate/mfu contract) — lower from those
        (entry,) = pred._compiled.values()
        txt = entry.fn.lower(*entry.arg_structs).compile().as_text()
        assert re.search(r"s8\[\d", txt), "no int8 parameter in HLO"
        assert "convert" in txt, "dequant not inside the executable"


class TestDistributedHLOSignatures:
    """The collective 'signature' of each parallelism mode, pinned on
    compiled HLO: the cheapest regression guard for the mechanisms the
    bench can't measure without hardware."""

    def test_ring_attention_permutes_never_gathers(self):
        """Ring attention must rotate K/V blocks (collective-permute)
        and must NOT fall back to all-gathering the full sequence —
        that would silently forfeit the O(L/n) memory the mode exists
        for."""
        from paddle_tpu.dist import env as denv
        from paddle_tpu.dist.ring_attention import ring_attention
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:4]), ("sp",))
        denv.set_mesh(mesh)
        try:
            q = jnp.ones((2, 4, 16, 8))

            def ra(q):
                t = pt.Tensor(q, _internal=True)
                return ring_attention(t, t, t, axis_name="sp",
                                      causal=True)._data

            with mesh:
                txt = jax.jit(ra).lower(q).compile().as_text()
        finally:
            denv.set_mesh(None)
        assert txt.count("collective-permute(") >= 1, "no ring rotation"
        assert txt.count("all-gather(") == 0, \
            "ring attention gathered the full sequence"

    def test_tp_block_megatron_signature(self):
        """Column->Row parallel pairs need exactly ONE all-reduce per
        row-parallel output (attn proj + mlp fc2 = 2 for a GPT block)
        and ZERO weight all-gathers — the Megatron communication
        contract the TP layers exist to honor."""
        from paddle_tpu.core import dispatch
        from paddle_tpu.dist import env as denv
        from paddle_tpu.models.nlp.gpt import GPTBlock, gpt_tiny
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        denv.set_mesh(mesh)
        try:
            pt.seed(0)
            cfg = gpt_tiny(dropout=0.0)
            blk = GPTBlock(cfg)
            blk.eval()
            x = jnp.ones((2, 16, cfg.hidden))

            def fwd(x):
                with dispatch.no_grad(), dispatch.fresh_tape():
                    return blk(pt.Tensor(x, _internal=True))._data

            with mesh:
                txt = jax.jit(fwd).lower(x).compile().as_text()
        finally:
            denv.set_mesh(None)
        assert txt.count("all-reduce(") == 2, \
            f"expected 2 partial-sum all-reduces, got " \
            f"{txt.count('all-reduce(')}"
        assert txt.count("all-gather(") == 0, "weights were all-gathered"


    def test_dp_step_keeps_the_batch_sharded(self):
        """Data parallelism must leave each device on ITS rows: no
        all-gather may rebuild global-batch x vocabulary logits (a
        replicating sharding constraint on the LM head once did, and
        every device then ran the whole batch), and per-device
        temporaries must not grow with the device count at a fixed
        per-device batch."""
        from paddle_tpu import distributed as dist
        from paddle_tpu.models.nlp.gpt import GPT, gpt_loss, gpt_tiny

        per_device, L = 2, 64
        temp = {}
        for n in (2, 8):
            mesh = dist.init_mesh({"data": n}, devices=jax.devices()[:n])
            try:
                pt.seed(0)
                cfg = gpt_tiny(dropout=0.0)
                model = GPT(cfg)
                step = dist.DistributedTrainStep(
                    model, optim.AdamW(parameters=model.parameters(),
                                       learning_rate=1e-3),
                    gpt_loss, mesh=mesh)
                B = per_device * n
                ids = np.zeros((B, L), "int32")
                step(ids, ids)
                exe = step.compiled()
            finally:
                dist.set_mesh(None)
            for shape in re.findall(r"= \w+\[([\d,]+)\]\S* all-gather",
                                    exe.as_text()):
                numel = int(np.prod([int(d) for d in shape.split(",")]))
                assert numel < B * L * cfg.vocab_size, \
                    f"all-gather [{shape}] rebuilds the global-batch logits"
            temp[n] = exe.memory_analysis().temp_size_in_bytes
        assert temp[8] <= 1.25 * temp[2], \
            f"per-device temporaries grow with the mesh: {temp}"


class TestStaticAMPHLO:
    def test_amp_step_is_one_guarded_bf16_executable(self, static_mode):
        """The fluid.contrib.mixed_precision step must stay ONE
        executable: list-driven bf16 casts present on the matmul path,
        the inf-guard select fused in, and the loss-scaling state
        updated through the same donated-alias mechanism as optimizer
        slots (no second program, no host round-trip)."""
        from paddle_tpu.fluid.contrib.mixed_precision import decorate

        pt.seed(0)
        prog = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(prog, startup):
            x = fluid.data(name="x", shape=[16, 8])
            y = fluid.data(name="y", shape=[16, 1])
            h = fluid.layers.fc(x, size=16, act="relu")
            out = fluid.layers.fc(h, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(out, y))
            opt = decorate(fluid.optimizer.SGD(learning_rate=0.1),
                           init_loss_scaling=256.0)
            opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        feed = _train_feed(prog)
        txt, compiled = _compiled_text(exe, prog, feed, [loss], False)
        # (a) white-list casts made it into the compiled program
        assert "bf16" in txt, "no bf16 anywhere: list casts lost"
        # (b) the inf-guarded update lowered to selects
        assert "select(" in txt
        # (c) scaling state rides the donated persistables (aliased,
        # not copied back through host)
        assert "@amp@scale" in compiled.updated
        assert "@amp@good" in compiled.updated
        aliases = txt.count("input_output_alias")
        assert aliases >= 1
