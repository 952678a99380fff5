"""Book-style e2e NLP tests (model: reference tests/book/test_word2vec.py,
test_understand_sentiment.py, test_machine_translation.py + the BERT/GPT
recipes): each model trains a few steps on synthetic data, loss decreases."""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
import paddle_tpu.optim as optim
from paddle_tpu.models.nlp import (
    NGramLM, SkipGram, skipgram_loss, ConvSentiment, StackedLSTMSentiment,
    WMTTransformer, wmt_loss, BertForPretraining, bert_tiny,
    bert_pretrain_loss, GPT, gpt_tiny, gpt_loss)
from paddle_tpu.models.rec import TwoTowerRecommender, DeepFM, rating_loss

VOCAB = 120


def _fit(model, loss_fn, batch, steps=10, lr=1e-2):
    opt = optim.Adam(lr, parameters=model.parameters())
    step = pt.TrainStep(model, opt, loss_fn)
    return [float(step(*batch)) for _ in range(steps)]


class TestWord2Vec:
    def test_ngram_lm_trains(self):
        rng = np.random.RandomState(0)
        ctx = rng.randint(0, VOCAB, (64, 4)).astype("int64")
        nxt = ctx[:, 0]  # learnable deterministic mapping
        losses = _fit(NGramLM(VOCAB, 16, 64),
                      lambda m, c, t: F.cross_entropy(m(c), t), (ctx, nxt))
        assert losses[-1] < losses[0] * 0.5, losses

    def test_skipgram_negative_sampling(self):
        rng = np.random.RandomState(0)
        center = rng.randint(0, VOCAB, (64,)).astype("int64")
        context = rng.randint(0, VOCAB, (64, 5)).astype("int64")
        label = np.zeros((64, 5), "float32")
        label[:, 0] = 1.0  # first candidate is the true context
        losses = _fit(SkipGram(VOCAB, 16), skipgram_loss,
                      (center, context, label))
        assert losses[-1] < losses[0], losses


class TestSentiment:
    def _data(self):
        rng = np.random.RandomState(0)
        ids = rng.randint(2, VOCAB, (32, 16)).astype("int64")
        y = (ids[:, 0] > VOCAB // 2).astype("int64")  # first-token rule
        return ids, y

    def test_conv_net(self):
        ids, y = self._data()
        losses = _fit(ConvSentiment(VOCAB, 32, 16),
                      lambda m, i, t: F.cross_entropy(m(i), t), (ids, y))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_stacked_lstm(self):
        ids, y = self._data()
        losses = _fit(StackedLSTMSentiment(VOCAB, 32, 32, num_layers=2),
                      lambda m, i, t: F.cross_entropy(m(i), t), (ids, y),
                      steps=12)
        assert losses[-1] < losses[0] * 0.8, losses


class TestMachineTranslation:
    def test_wmt_transformer_trains_and_decodes(self):
        rng = np.random.RandomState(0)
        src = rng.randint(2, 50, (16, 10)).astype("int64")
        tgt_full = np.concatenate(
            [np.zeros((16, 1), "int64"), (src + 1) % 60], axis=1)
        tgt_in, tgt_lab = tgt_full[:, :-1], tgt_full[:, 1:]
        model = WMTTransformer(50, 60, d_model=32, nhead=4, num_layers=2,
                               dim_feedforward=64, dropout=0.0, max_len=32)
        losses = _fit(model,
                      lambda m, s, ti, tl: wmt_loss(m, s, ti, tl, pad_id=None),
                      (src, tgt_in, tgt_lab), steps=12, lr=3e-3)
        assert losses[-1] < losses[0] * 0.8, losses
        out = model.greedy_decode(src[:2], max_len=6)
        assert out.shape == [2, 6]
        assert int(out[0, 0]) == model.bos_id


class TestBertPretrain:
    def test_mlm_nsp_loss_decreases(self):
        rng = np.random.RandomState(0)
        cfg = bert_tiny(dropout=0.0)
        B, L = 8, 24
        ids = rng.randint(0, cfg.vocab_size, (B, L)).astype("int64")
        tt = np.zeros((B, L), "int64")
        am = np.ones((B, L), "int64")
        mlm = np.where(rng.rand(B, L) < 0.15, ids, -100).astype("int64")
        nsp = rng.randint(0, 2, (B,)).astype("int64")
        model = BertForPretraining(cfg)
        losses = _fit(model, lambda m, *b: bert_pretrain_loss(m, *b),
                      (ids, tt, am, mlm, nsp), steps=10, lr=3e-3)
        assert losses[-1] < losses[0] * 0.8, losses


def _full_width_loss(model, ids, tt, am, mlm, nsp):
    """The loss as it was before the compact head: every position's logits."""
    logits, nsp_logits = model(ids, tt, am)
    return F.cross_entropy(
        pt.reshape(logits, [-1, logits.shape[-1]]), pt.reshape(mlm, [-1]),
        ignore_index=-100) + F.cross_entropy(nsp_logits, nsp)


class TestBertCompactHead:
    """``bert_pretrain_loss`` runs the MLM head over the labelled positions
    gathered ``compact_rows(B x L)`` rows at a time, in as many passes as
    the count of labels needs: the full-width head's loss and gradients."""

    B, L = 8, 16                  # 128 positions, so K = 32
    K = 32
    LABELLED = {"none": 0, "one": 1, "under_k": 12, "exactly_k": 32,
                "k_plus_1": 33, "every_position": 128, "one_row": "row"}

    def _batch(self, labelled, seed=0):
        rng = np.random.RandomState(seed)
        cfg = bert_tiny(dropout=0.0)
        n = self.B * self.L
        ids = rng.randint(0, cfg.vocab_size, (self.B, self.L)).astype("int64")
        tt = (np.arange(self.L)[None] >= self.L // 2).astype("int64") \
            * np.ones((self.B, 1), "int64")
        am = np.ones((self.B, self.L), "int64")
        am[1, self.L - 3:] = 0
        at = np.arange(3 * self.L, 4 * self.L) if labelled == "row" \
            else rng.permutation(n)[:labelled]
        mlm = np.full(n, -100, "int64")
        mlm[at] = ids.reshape(-1)[at]
        nsp = rng.randint(0, 2, (self.B,)).astype("int64")
        return cfg, (ids, tt, am, mlm.reshape(self.B, self.L), nsp)

    def _eager(self, model, loss_fn, batch):
        for p in model.parameters():
            p.grad = None
        loss = loss_fn(model, *[pt.to_tensor(a) for a in batch])
        loss.backward()
        return float(loss), {n: p.grad.numpy() for n, p
                             in model.named_parameters()}

    def _train_step(self, model, loss_fn, batch, step=None):
        """(loss, gradients) read off one SGD step of rate 1, then undone."""
        before = {n: p.numpy().copy() for n, p in model.named_parameters()}
        step = step or pt.TrainStep(
            model, optim.SGD(1.0, parameters=model.parameters()), loss_fn)
        loss = float(step(*batch))
        grads = {n: before[n] - p.numpy()
                 for n, p in model.named_parameters()}
        model.set_state_dict({n: pt.to_tensor(a) for n, a in before.items()})
        return loss, grads

    def test_compact_rows_is_a_quarter_in_whole_sublanes(self):
        from paddle_tpu.models.nlp.bert import compact_rows

        assert [compact_rows(n) for n in (128, 24 * 512, 128 * 128, 100, 8)] \
            == [32, 3072, 4096, 32, 8]

    @pytest.mark.parametrize("mode", ["eager", "train_step"])
    @pytest.mark.parametrize("labelled", list(LABELLED))
    def test_loss_and_gradients_are_the_full_width_heads(self, labelled, mode):
        cfg, batch = self._batch(self.LABELLED[labelled])
        pt.seed(0)
        model = BertForPretraining(cfg)
        run = self._eager if mode == "eager" else self._train_step
        want_loss, want = run(model, _full_width_loss, batch)
        got_loss, got = run(model, bert_pretrain_loss, batch)
        assert abs(got_loss - want_loss) <= 1e-5 * max(1.0, abs(want_loss))
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=0,
                                       atol=1e-5, err_msg=name)
        assert np.isfinite(got_loss)

    def test_forward_without_positions_is_what_it_was(self):
        cfg, (ids, tt, am, _, _) = self._batch(12)
        pt.seed(0)
        model = BertForPretraining(cfg)
        model.eval()
        ids, tt, am = (pt.to_tensor(a) for a in (ids, tt, am))
        logits, nsp = model(ids, tt, am)
        seq, pooled = model.bert(ids, tt, am)
        h = model.transform_norm(F.gelu(model.transform(seq)))
        was = pt.matmul(h, pt.transpose(model.bert.embeddings.word.weight,
                                        [1, 0])) + model.mlm_bias
        assert logits.shape == [self.B, self.L, cfg.vocab_size]
        np.testing.assert_array_equal(logits.numpy(), was.numpy())
        np.testing.assert_array_equal(nsp.numpy(), model.nsp(pooled).numpy())
        at = np.array([0, 5, 17, 127, 64, 0, 0, 0], "int64")
        picked, nsp_too = model(ids, tt, am, masked_positions=pt.to_tensor(at))
        assert picked.shape == [8, cfg.vocab_size]
        np.testing.assert_allclose(
            picked.numpy(), logits.numpy().reshape(-1, cfg.vocab_size)[at],
            rtol=0, atol=1e-5)
        np.testing.assert_array_equal(nsp_too.numpy(), nsp.numpy())

    def test_one_compiled_step_loops_the_head_over_k_rows(self):
        """The step's text has one loop (the head makes its gradients inside
        it), its decoder product has K rows and no product has every
        position's, and the one compile serves label counts on both sides of
        K."""
        cfg, under = self._batch(12)
        _, over = self._batch(33, seed=1)
        pt.seed(0)
        model = BertForPretraining(cfg)
        traced = []

        def loss_fn(*a):
            traced.append(1)
            return bert_pretrain_loss(*a)

        step = pt.TrainStep(
            model, optim.SGD(1.0, parameters=model.parameters()), loss_fn)
        for batch in (under, over, under):
            want_loss, want = self._train_step(model, _full_width_loss, batch)
            got_loss, got = self._train_step(model, loss_fn, batch, step)
            assert abs(got_loss - want_loss) <= 1e-5 * abs(want_loss)
            for name in want:
                np.testing.assert_allclose(got[name], want[name], rtol=0,
                                           atol=1e-5, err_msg=name)
        assert len(traced) == 1 and len(step._compiled) == 1
        (sig, fn), = step._compiled.items()
        assert fn._cache_size() == 1
        text = fn.lower(*step._arg_structs[sig]).as_text()
        v = cfg.vocab_size
        assert text.count("stablehlo.while") == 1
        assert "stablehlo.case" not in text
        assert f"-> tensor<{self.K}x{v}xf32>" in text      # a pass's decoder
        assert f"x{self.L}x{v}xf32>" not in text           # no full-width one

    @pytest.mark.parametrize("labelled", ["under_k", "k_plus_1"])
    def test_float16_under_a_loss_scale_keeps_the_heads_gradients(
            self, labelled):
        """The head makes its gradients before the loss's cotangent, and so
        the loss scale, is known: they are those of the loss's sum (the
        logits' is ``softmax - onehot``, O(1)), so float16 holds them as it
        holds a scaled one's, and the step agrees with the full-width head's
        under the same scaler."""
        from paddle_tpu import amp

        cfg, batch = self._batch(self.LABELLED[labelled])
        pt.seed(0)
        model = BertForPretraining(cfg)

        def half(loss_fn):
            def run(*a):
                with amp.auto_cast(dtype="float16"):
                    return loss_fn(*a)
            return run

        def one(loss_fn):
            step = pt.TrainStep(
                model, optim.SGD(1.0, parameters=model.parameters()),
                half(loss_fn), scaler=amp.StaticLossScaler(2.0 ** 12))
            out = self._train_step(model, None, batch, step)
            assert not bool(step.last_found_inf)
            return out

        want_loss, want = one(_full_width_loss)
        got_loss, got = one(bert_pretrain_loss)
        assert abs(got_loss - want_loss) <= 2e-3 * abs(want_loss)
        head = [n for n in want if n.startswith(("transform", "mlm_bias"))]
        assert len(head) == 5
        for name in want:
            if name.endswith("k_proj.bias"):  # a softmax ignores a key bias:
                continue                      # zero but for rounding
            scale = np.abs(want[name]).max()
            assert scale > 0, name
            np.testing.assert_allclose(got[name], want[name], rtol=0,
                                       atol=0.02 * scale, err_msg=name)


class TestGPT:
    def test_gpt_trains(self):
        rng = np.random.RandomState(0)
        cfg = gpt_tiny(dropout=0.0)
        ids = rng.randint(0, cfg.vocab_size, (4, 32)).astype("int64")
        labels = np.roll(ids, -1, axis=1)
        losses = _fit(GPT(cfg), gpt_loss, (ids, labels), steps=8, lr=3e-3)
        assert losses[-1] < losses[0] * 0.8, losses

    def test_generate_kv_cache_matches_full_forward(self):
        """Incremental KV-cache decode must agree with the dense forward."""
        cfg = gpt_tiny(dropout=0.0)
        pt.seed(3)
        model = GPT(cfg)
        model.eval()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, (2, 8)).astype("int64")
        out = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                             temperature=0.0)
        assert out.shape == [2, 12]
        # greedy reference: re-run the full forward each step
        cur = ids
        for _ in range(4):
            logits = model(pt.to_tensor(cur))
            nxt = np.asarray(logits.numpy())[:, -1].argmax(-1)[:, None]
            cur = np.concatenate([cur, nxt.astype("int64")], axis=1)
        np.testing.assert_array_equal(out.numpy(), cur)

    def test_generate_xla_matches_eager_generate(self):
        """The single-executable decode (static KV cache + lax.scan)
        must reproduce the eager greedy decode token-for-token, and
        reuse its compiled executable across same-signature calls."""
        cfg = gpt_tiny(dropout=0.0)
        pt.seed(3)
        model = GPT(cfg)
        model.eval()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, (2, 8)).astype("int64")
        eager = model.generate(pt.to_tensor(ids), max_new_tokens=6,
                               temperature=0.0)
        fused = model.generate_xla(ids, max_new_tokens=6, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(eager.numpy()),
                                      np.asarray(fused.numpy()))
        assert len(model._xla_gen_cache) == 1
        model.generate_xla(ids, max_new_tokens=6, temperature=0.0)
        assert len(model._xla_gen_cache) == 1
        # sampled path: right shape, tokens in range
        samp = model.generate_xla(ids, max_new_tokens=4, temperature=1.0,
                                  top_k=5, seed=7)
        s = np.asarray(samp.numpy())
        assert s.shape == (2, 12)
        assert (s >= 0).all() and (s < cfg.vocab_size).all()


class TestRecommender:
    def test_two_tower_trains(self):
        rng = np.random.RandomState(0)
        n = 64
        feats = [rng.randint(0, hi, (n,)).astype("int64")
                 for hi in (40, 2, 7, 21, 50, 19)]
        rating = (feats[0] % 5).astype("float32") + 0.5
        model = TwoTowerRecommender(40, 50)
        losses = _fit(model, rating_loss, (*feats, rating), steps=12, lr=5e-3)
        assert losses[-1] < losses[0] * 0.8, losses

    def test_deepfm_trains(self):
        rng = np.random.RandomState(0)
        n = 64
        fields = [10, 20, 30]
        ids = [rng.randint(0, v, (n,)).astype("int64") for v in fields]
        y = ((ids[0] + ids[1]) % 2).astype("float32")
        model = DeepFM(fields, embed_dim=8, hidden=(32, 32))

        def loss_fn(m, a, b, c, t):
            return F.binary_cross_entropy_with_logits(m(a, b, c), t)

        losses = _fit(model, loss_fn, (*ids, y), steps=12, lr=5e-3)
        assert losses[-1] < losses[0], losses


class TestGPTXlaWeights:
    def test_generate_xla_sees_weight_updates(self):
        """The cached decode executable must use CURRENT weights
        (constant-folding regression: params are jit arguments)."""
        cfg = gpt_tiny(dropout=0.0)
        pt.seed(5)
        model = GPT(cfg)
        model.eval()
        ids = np.random.RandomState(2).randint(
            0, cfg.vocab_size, (2, 6)).astype("int64")
        out1 = np.asarray(model.generate_xla(
            ids, max_new_tokens=4, temperature=0.0).numpy())
        for p in model.parameters():
            p._data = p._data * 0.0  # zero the model
        out2 = np.asarray(model.generate_xla(
            ids, max_new_tokens=4, temperature=0.0).numpy())
        eager2 = np.asarray(model.generate(
            pt.to_tensor(ids), max_new_tokens=4, temperature=0.0).numpy())
        np.testing.assert_array_equal(out2, eager2)  # matches CURRENT model
        # zero weights -> uniform logits -> argmax token 0 everywhere;
        # the pre-zeroing decode must differ (constant-folding signal)
        assert (out2[:, 6:] == 0).all()
        assert not np.array_equal(out1[:, 6:], out2[:, 6:])
