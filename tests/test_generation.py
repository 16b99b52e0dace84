"""Serving-path tests: paged KV cache kernel, cached decode, generate(),
continuous-batching engine (SURVEY.md §7 phase 10 / BASELINE.json config 5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # distributed/parity suites: excluded from the fast gate

import paddle_tpu as paddle
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.tensor import Tensor, as_array


def _tiny_model(vocab=97, hidden=32, layers=2, heads=4, seq=64):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads, seq=seq)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


# ---------------------------------------------------------------------------
# paged KV cache primitives
# ---------------------------------------------------------------------------


class TestPagedKV:
    def test_update_and_gather_roundtrip(self):
        kvh, n_pages, ps, hd = 2, 8, 4, 8
        kp, vp = pa.alloc_pages(n_pages, ps, kvh, hd)
        tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)  # 2 seqs
        lens = jnp.asarray([0, 5], jnp.int32)
        rng = np.random.RandomState(0)
        k_new = jnp.asarray(rng.randn(2, kvh, hd), jnp.float32)
        v_new = jnp.asarray(rng.randn(2, kvh, hd), jnp.float32)
        kp, vp = pa.update_paged_kv_cache(kp, vp, k_new, v_new, tables, lens)
        # seq0 token -> page 0 slot 0; seq1 token 5 -> page 3 slot 1
        np.testing.assert_allclose(kp[:, 0, 0], k_new[0], rtol=1e-6)
        np.testing.assert_allclose(kp[:, 3, 1], k_new[1], rtol=1e-6)
        np.testing.assert_allclose(vp[:, 0, 0], v_new[0], rtol=1e-6)

    def test_prefill_scatter(self):
        kvh, n_pages, ps, hd = 2, 8, 4, 8
        kp, vp = pa.alloc_pages(n_pages, ps, kvh, hd)
        tables = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
        rng = np.random.RandomState(1)
        s = 10
        kseq = jnp.asarray(rng.randn(1, s, kvh, hd), jnp.float32)
        vseq = jnp.asarray(rng.randn(1, s, kvh, hd), jnp.float32)
        kp, vp = pa.prefill_paged_kv_cache(kp, vp, kseq, vseq, tables,
                                           jnp.asarray([s], jnp.int32))
        for pos in range(s):
            page = tables[0, pos // ps]
            np.testing.assert_allclose(kp[:, page, pos % ps],
                                       kseq[0, pos].T.T.transpose(0, 1),
                                       rtol=1e-6)

    def test_paged_attention_matches_dense(self):
        rng = np.random.RandomState(2)
        b, qh, kvh, hd, ps, pps = 2, 4, 2, 16, 8, 4
        n_pages = 16
        q = jnp.asarray(rng.randn(b, qh, hd), jnp.float32)
        kp = jnp.asarray(rng.randn(kvh, n_pages, ps, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(kvh, n_pages, ps, hd), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(n_pages)[: b * pps].reshape(b, pps), jnp.int32)
        lens = jnp.asarray([13, 27], jnp.int32)
        ref = pa.paged_attention_xla(q, kp, vp, tables, lens)
        out = pa.paged_attention(q, kp, vp, tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_paged_attention_gqa_group1(self):
        rng = np.random.RandomState(3)
        b, qh, kvh, hd, ps, pps = 1, 2, 2, 8, 4, 2
        q = jnp.asarray(rng.randn(b, qh, hd), jnp.float32)
        kp = jnp.asarray(rng.randn(kvh, 4, ps, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(kvh, 4, ps, hd), jnp.float32)
        tables = jnp.asarray([[1, 3]], jnp.int32)
        lens = jnp.asarray([6], jnp.int32)
        ref = pa.paged_attention_xla(q, kp, vp, tables, lens)
        out = pa.paged_attention(q, kp, vp, tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# dense-cache incremental decode == full forward
# ---------------------------------------------------------------------------


class TestCachedDecode:
    def test_incremental_matches_full_forward(self):
        m, cfg = _tiny_model()
        rng = np.random.RandomState(0)
        b, s = 2, 10
        ids = rng.randint(0, cfg.vocab_size, (b, s))
        full = as_array(m(Tensor(ids)))  # [b, s, vocab]

        caches = m.init_kv_caches(b, s)
        # prefill first 6, then decode one token at a time
        logits_p, caches = m.forward_cached(Tensor(ids[:, :6]), caches, 0)
        outs = [as_array(logits_p)]
        for t in range(6, s):
            logits_t, caches = m.forward_cached(
                Tensor(ids[:, t:t + 1]), caches, t)
            outs.append(as_array(logits_t))
        inc = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# generate()
# ---------------------------------------------------------------------------


class TestGenerate:
    def test_greedy_matches_nocache_argmax(self):
        m, cfg = _tiny_model()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, (2, 5))
        out, scores = m.generate(Tensor(ids), max_new_tokens=6,
                                 decode_strategy="greedy_search")
        out = np.asarray(as_array(out))
        assert out.shape == (2, 6)
        # reference: greedy loop re-running the full forward every step
        cur = ids.copy()
        for _ in range(6):
            logits = as_array(m(Tensor(cur)))[:, -1, :]
            nxt = np.asarray(jnp.argmax(logits, axis=-1))[:, None]
            cur = np.concatenate([cur, nxt], axis=1)
        np.testing.assert_array_equal(out, cur[:, 5:])

    def test_sampling_seeded_and_in_vocab(self):
        m, cfg = _tiny_model()
        ids = np.asarray([[1, 2, 3]])
        o1, _ = m.generate(Tensor(ids), max_new_tokens=5,
                           decode_strategy="sampling", top_k=10,
                           temperature=0.8, seed=7)
        o2, _ = m.generate(Tensor(ids), max_new_tokens=5,
                           decode_strategy="sampling", top_k=10,
                           temperature=0.8, seed=7)
        a1, a2 = np.asarray(as_array(o1)), np.asarray(as_array(o2))
        np.testing.assert_array_equal(a1, a2)
        assert ((a1 >= 0) & (a1 < cfg.vocab_size)).all()

    def test_eos_stops_early(self):
        m, cfg = _tiny_model()
        ids = np.asarray([[1, 2, 3]])
        logits = as_array(m(Tensor(ids)))[:, -1, :]
        eos = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
        out, _ = m.generate(Tensor(ids), max_new_tokens=8,
                            decode_strategy="greedy_search",
                            eos_token_id=eos, pad_token_id=0)
        out = np.asarray(as_array(out))
        assert out[0, 0] == eos
        # everything after the first token is pad (loop exited)
        assert (out[0, 1:] == 0).all()

    def test_top_p_masks_tail(self):
        from paddle_tpu.models.generation import sample_logits

        logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.05, 0.05]]))
        toks = set()
        for i in range(30):
            t, _ = sample_logits(logits, jax.random.PRNGKey(i),
                                 "sampling", 1.0, 0, 0.7)
            toks.add(int(t[0]))
        assert toks <= {0, 1}


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------


class TestServingEngine:
    def test_greedy_parity_with_generate(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, cfg.vocab_size, (n,))
                   for n in (4, 6, 4)]
        engine = ServingEngine(m, max_batch=2, max_seq_len=32,
                               page_size=8,
                               decode_strategy="greedy_search")
        rids = [engine.add_request(p, max_new_tokens=5) for p in prompts]
        finished = engine.run()
        assert sorted(f.request_id for f in finished) == sorted(rids)
        by_rid = {f.request_id: f for f in finished}
        for rid, p in zip(rids, prompts):
            ref, _ = m.generate(Tensor(p[None, :]), max_new_tokens=5,
                                decode_strategy="greedy_search")
            np.testing.assert_array_equal(
                by_rid[rid].output_ids,
                np.asarray(as_array(ref))[0])

    def test_stale_slot_does_not_corrupt_reused_pages(self):
        # regression: a finished slot's stale block table must not keep
        # writing K/V into pages that were freed and reassigned to a new
        # request in a different slot
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        rng = np.random.RandomState(7)
        long0 = rng.randint(0, cfg.vocab_size, (4,))
        short1 = rng.randint(0, cfg.vocab_size, (3,))
        short2 = rng.randint(0, cfg.vocab_size, (3,))
        late3 = rng.randint(0, cfg.vocab_size, (4,))
        engine = ServingEngine(m, max_batch=3, max_seq_len=16, page_size=8,
                               decode_strategy="greedy_search")
        rids = [engine.add_request(long0, max_new_tokens=10),
                engine.add_request(short1, max_new_tokens=1),
                engine.add_request(short2, max_new_tokens=1),
                engine.add_request(late3, max_new_tokens=10)]
        finished = {f.request_id: f for f in engine.run()}
        for rid, p, n in [(rids[0], long0, 10), (rids[3], late3, 10)]:
            ref, _ = m.generate(Tensor(p[None, :]), max_new_tokens=n,
                                decode_strategy="greedy_search")
            np.testing.assert_array_equal(
                finished[rid].output_ids, np.asarray(as_array(ref))[0])

    def test_prompt_overflow_rejected(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        engine = ServingEngine(m, max_batch=1, max_seq_len=16, page_size=8)
        with pytest.raises(ValueError):
            engine.add_request(np.arange(12) % cfg.vocab_size,
                               max_new_tokens=8)

    def test_pages_freed_and_reused(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        engine = ServingEngine(m, max_batch=2, max_seq_len=16, page_size=8,
                               decode_strategy="greedy_search")
        total_pages = len(engine._free_pages)
        for i in range(5):
            engine.add_request(np.asarray([1, 2, 3]), max_new_tokens=3)
        engine.run()
        assert len(engine._free_pages) == total_pages
        assert not engine.has_work()


class TestInferenceConfigPredictor:
    def test_predictor_roundtrip(self, tmp_path):
        import paddle_tpu.inference as infer
        from paddle_tpu import jit as pjit
        from paddle_tpu import nn

        paddle.seed(0)
        layer = nn.Linear(4, 3)
        layer.eval()
        x = Tensor(np.random.RandomState(0).randn(2, 4).astype(np.float32))
        want = np.asarray(as_array(layer(x)))
        path = str(tmp_path / "model")
        pjit.save(layer, path, input_spec=[x])
        cfg = infer.Config(path)
        cfg.enable_memory_optim()
        pred = infer.create_predictor(cfg)
        out = pred.run([np.asarray(as_array(x))])
        np.testing.assert_allclose(out[0], want, rtol=1e-5)


class TestBatchedPrefill:
    def test_simultaneous_admissions_prefill_in_one_batch(self):
        """Requests queued before the engine runs must prefill together in
        ONE compiled call (VERDICT round-1: admission must not serialize
        at batch 1)."""
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        rng = np.random.RandomState(7)
        engine = ServingEngine(m, max_batch=4, max_seq_len=32, page_size=8,
                               decode_strategy="greedy_search")
        calls = []
        orig = engine._prefill_batch
        engine._prefill_batch = lambda new: (calls.append(len(new)),
                                             orig(new))[-1]
        # plain public flow: queue four requests, then run — admission is
        # deferred to step(), so all four prefill in ONE batched call
        for n in (4, 6, 5, 3):
            engine.add_request(rng.randint(0, cfg.vocab_size, (n,)),
                               max_new_tokens=4)
        finished = engine.run()
        assert calls[0] == 4, calls  # one batched prefill of all four
        assert len(finished) == 4
        # parity: batched prefill must not change greedy outputs
        by_rid = {f.request_id: f for f in finished}
        for rid in range(4):
            p = by_rid[rid].prompt_ids
            ref, _ = m.generate(Tensor(p[None, :]), max_new_tokens=4,
                                decode_strategy="greedy_search")
            np.testing.assert_array_equal(by_rid[rid].output_ids,
                                          np.asarray(as_array(ref))[0])


class TestServingHardening:
    """Round-3: on-demand paging, preemption, bf16 pages, device-side
    first-token sampling, cached params (round-2 verdict weak #5)."""

    def test_kv_pages_in_model_dtype(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        # cast model to bf16: pages must follow
        import paddle_tpu as paddle
        paddle.amp.decorate(m, level="O2", dtype="bfloat16")
        engine = ServingEngine(m, max_batch=2, max_seq_len=16, page_size=8)
        import jax.numpy as jnp
        assert engine.k_pages[0].dtype == jnp.bfloat16
        assert engine.v_pages[0].dtype == jnp.bfloat16

    def test_admission_takes_prompt_pages_only(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        engine = ServingEngine(m, max_batch=2, max_seq_len=32, page_size=8,
                               decode_strategy="greedy_search")
        total = len(engine._free_pages)  # 2 * 4 pages
        engine.add_request(np.asarray([1, 2, 3]), max_new_tokens=20)
        engine._admit()
        # 3-token prompt -> ONE page reserved, not max_seq_len/page_size=4
        assert total - len(engine._free_pages) == 1
        engine.run()
        assert len(engine._free_pages) == total

    def test_decode_grows_pages_on_demand(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        engine = ServingEngine(m, max_batch=1, max_seq_len=32, page_size=8,
                               decode_strategy="greedy_search")
        rid = engine.add_request(np.asarray([1, 2, 3, 4, 5, 6, 7]),
                                 max_new_tokens=12)
        engine._admit()
        assert engine.slots[0].n_pages == 1
        out = engine.run()
        # 7 prompt + 12 generated - 1 unfed = 18 cached -> 3 pages peaked
        assert out[0].request_id == rid
        assert len(out[0].output_ids) == 12

    def test_preemption_requeues_and_completes(self):
        """Oversubscribed pool: the youngest slot is evicted, re-prefills
        later, and still returns the same greedy tokens."""
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        rng = np.random.RandomState(11)
        pa = rng.randint(0, cfg.vocab_size, (6,))
        pb = rng.randint(0, cfg.vocab_size, (6,))
        # pool of 4 pages (max_batch=2 * 16/8); two requests that each
        # need 2 pages at admission and grow to need 2 more
        engine = ServingEngine(m, max_batch=2, max_seq_len=16, page_size=8,
                               decode_strategy="greedy_search")
        ra = engine.add_request(pa, max_new_tokens=9)
        rb = engine.add_request(pb, max_new_tokens=9)
        finished = {f.request_id: f for f in engine.run()}
        assert set(finished) == {ra, rb}
        for rid, p in ((ra, pa), (rb, pb)):
            ref, _ = m.generate(Tensor(p[None, :]), max_new_tokens=9,
                                decode_strategy="greedy_search")
            np.testing.assert_array_equal(finished[rid].output_ids,
                                          np.asarray(as_array(ref))[0])

    def test_params_pytree_cached(self):
        from paddle_tpu.inference import ServingEngine

        m, cfg = _tiny_model()
        engine = ServingEngine(m, max_batch=1, max_seq_len=16, page_size=8,
                               decode_strategy="greedy_search")
        calls = {"n": 0}
        orig = m.parameters_pytree

        def counting():
            calls["n"] += 1
            return orig()

        m.parameters_pytree = counting
        engine.add_request(np.asarray([1, 2, 3]), max_new_tokens=6)
        engine.run()
        assert calls["n"] <= 1  # built once, reused across decode steps
