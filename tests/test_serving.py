"""Continuous-batching serving engine tests.

Reference contract: the block_multi_head_attention serving-op family +
fused_multi_transformer cached decoding — paged-cache generation must
reproduce the model's own greedy decode exactly, across mixed prompt
lengths, admission waves, and block-boundary growth.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import (BlockManager, LlamaPagedEngine,  # noqa: E402
                                  PagedEngine)
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,  # noqa: E402
                               LlamaConfig, LlamaForCausalLM)
from paddle_tpu.serving import SchedulerConfig  # noqa: E402

from served import (assert_greedy, model_of, models,  # noqa: E402,F401
                    watch_evictions)


def _tiny_model():
    # the file's one dense model: no test mutates a weight, and engines
    # over one model share compiled tick programs (serving.
    # _PAGED_JIT_CACHE): this suite is decode parity, not compile timing
    return model_of("dense")


def _tiny_gpt():
    return model_of("gpt")


class TestBlockManager:
    def test_allocate_release(self):
        bm = BlockManager(5)          # blocks 1..4 usable (0 reserved)
        a = bm.allocate(3)
        assert 0 not in a and len(set(a)) == 3
        assert bm.available == 1
        with pytest.raises(MemoryError):
            bm.allocate(2)
        bm.release(a)
        assert bm.available == 4


class TestPagedEngineParity:
    def test_single_request_matches_model_generate(self):
        model = _tiny_model()
        rng = np.random.RandomState(0)
        prompt = [int(t) for t in rng.randint(1, 97, size=11)]
        eng = LlamaPagedEngine(model, max_batch=2, block_size=4,
                               num_blocks=32, max_blocks_per_seq=16)
        rid = eng.add_request(prompt, max_new_tokens=8)
        out = eng.run_to_completion()
        assert_greedy(model, prompt, out[rid], 8)

    @pytest.mark.slow
    # slow-marked (~15s, 870s tier-1 budget): paged-vs-dense parity
    # stays in tier-1 via the single-request llama case above and the
    # GPT full-recompute greedy case below; the mixed-length staggered
    # matrix runs in the full suite
    def test_mixed_lengths_and_staggered_admission(self):
        model = _tiny_model()
        rng = np.random.RandomState(1)
        prompts = [[int(t) for t in rng.randint(1, 97, size=n)]
                   for n in (3, 9, 17, 5)]
        eng = LlamaPagedEngine(model, max_batch=2, block_size=4,
                               num_blocks=64, max_blocks_per_seq=16)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out = eng.run_to_completion()
        # only 2 slots: requests 3/4 admitted after earlier ones finish
        for rid, p in zip(rids, prompts):
            assert_greedy(model, p, out[rid], 6)

    def test_block_growth_across_boundaries(self):
        model = _tiny_model()
        rng = np.random.RandomState(2)
        prompt = [int(t) for t in rng.randint(1, 97, size=6)]
        # block_size 4: seq grows 6 -> 18, crossing several boundaries
        eng = LlamaPagedEngine(model, max_batch=1, block_size=4,
                               num_blocks=16, max_blocks_per_seq=8)
        rid = eng.add_request(prompt, max_new_tokens=12)
        out = eng.run_to_completion()
        assert_greedy(model, prompt, out[rid], 12)
        # all blocks released after completion
        assert eng.bm.available == 15

    def test_eos_stops_early(self):
        model = _tiny_model()
        prompt = [5, 9, 2]
        geometry = dict(max_batch=1, block_size=4, num_blocks=16,
                        max_blocks_per_seq=8)
        plain = LlamaPagedEngine(model, **geometry)
        rid = plain.add_request(prompt, max_new_tokens=10)
        ref = plain.run_to_completion()[rid]
        assert_greedy(model, prompt, ref, 10)
        eos = ref[2]                  # force a stop at the 3rd token
        eng = LlamaPagedEngine(model, eos_id=eos, **geometry)
        rid = eng.add_request(prompt, max_new_tokens=10)
        out = eng.run_to_completion()
        assert out[rid] == ref[:3]

    def test_preemption_under_memory_pressure(self):
        """The reviewer's livelock repro: two slots that both need a 3rd
        block with 0 free must not spin — the youngest request is
        preempted (recompute-style), the other finishes, and BOTH still
        produce exactly the model's greedy tokens."""
        model = _tiny_model()
        rng = np.random.RandomState(4)
        p1 = [int(t) for t in rng.randint(1, 97, size=4)]
        p2 = [int(t) for t in rng.randint(1, 97, size=4)]
        eng = LlamaPagedEngine(model, max_batch=2, block_size=4,
                               num_blocks=5, max_blocks_per_seq=4)
        r1 = eng.add_request(p1, max_new_tokens=6)
        r2 = eng.add_request(p2, max_new_tokens=6)
        out = eng.run_to_completion(max_ticks=200)
        assert_greedy(model, p1, out[r1], 6)
        assert_greedy(model, p2, out[r2], 6)
        assert eng.bm.available == 4          # everything released

    def test_never_fitting_request_fails_at_submit(self):
        """A request that can never fit this replica's geometry is a
        terminal FAILED status at submit time — nothing raises, no other
        request's results are at risk, and the engine keeps serving."""
        from paddle_tpu.inference import RequestStatus
        model = _tiny_model()
        eng = LlamaPagedEngine(model, max_batch=1, block_size=4,
                               num_blocks=4, max_blocks_per_seq=2)
        bad = eng.add_request(list(range(1, 30)), max_new_tokens=4)
        assert eng.request_status(bad) == RequestStatus.FAILED
        assert bad in eng.rejected and "blocks" in eng.rejected[bad]
        assert "blocks" in eng.outcomes[bad].detail
        # the rejected request never entered the queue
        assert not eng.queue
        rid = eng.add_request([1, 2, 3], max_new_tokens=2)
        out = eng.run_to_completion()
        assert len(out[rid]) == 2
        assert bad not in out

    def test_request_validation(self):
        model = _tiny_model()
        eng = LlamaPagedEngine(model, max_batch=1, block_size=4,
                               num_blocks=8, max_blocks_per_seq=4)
        with pytest.raises(ValueError, match="non-empty"):
            eng.add_request([])
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request([1], max_new_tokens=0)


class TestSampling:
    def test_seeded_sampling_reproducible_and_greedy_unchanged(self):
        model = _tiny_model()
        rng = np.random.RandomState(8)
        prompt = [int(t) for t in rng.randint(1, 97, size=5)]

        def run(seed, temperature, top_p=0.9):
            eng = LlamaPagedEngine(model, max_batch=1, block_size=4,
                                   num_blocks=16, max_blocks_per_seq=8,
                                   seed=seed)
            rid = eng.add_request(prompt, max_new_tokens=8,
                                  temperature=temperature, top_p=top_p)
            return eng.run_to_completion()[rid]

        # greedy path ignores the seed entirely
        greedy = run(0, 0.0)
        assert greedy == run(123, 0.0)
        assert_greedy(model, prompt, greedy, 8)
        # sampling is reproducible per seed, and seeds differ
        s1, s2, s3 = run(7, 1.0), run(7, 1.0), run(9, 1.0)
        assert s1 == s2
        assert any(a != b for a, b in zip(s1, s3)) or s1 != s3

    def test_top_p_validation(self):
        model = _tiny_model()
        eng = LlamaPagedEngine(model, max_batch=1, block_size=4,
                               num_blocks=8, max_blocks_per_seq=4)
        with pytest.raises(ValueError, match="top_p"):
            eng.add_request([1, 2], top_p=0.0)


class TestGPTPagedEngine:
    # W = 8 here: the prompts leave 7, 1, 7 and 4 rows of left padding at
    # negative positions of the learned-position table
    @pytest.mark.parametrize("n_prompt", [1, 7, 9, 20])
    def test_gpt_matches_full_recompute_greedy(self, n_prompt):
        model = _tiny_gpt()
        rng = np.random.RandomState(5)
        prompt = [int(t) for t in rng.randint(1, 83, size=n_prompt)]

        eng = PagedEngine(model, max_batch=2, block_size=4,
                          num_blocks=32, max_blocks_per_seq=8)
        assert eng.prefill_width == 8
        rid = eng.add_request(prompt, max_new_tokens=6)
        out = eng.run_to_completion()
        assert_greedy(model, prompt, out[rid], 6)


# --------------------------------------------- the prefill program's shape
#: kind -> (the scheduler's budget, the W it gives)
_WIDTHS = {"block": (4, 4), "budget": (8, 8), "wide": (None, 12)}


def _width_engine(model, kind, **kw):
    """One engine of each way W comes about: the budget of one block, a
    budget of two, and the engine's own widest chunk (three blocks: a
    decode batch of 3 lanes x block 4)."""
    budget, width = _WIDTHS[kind]
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_blocks_per_seq", 16)
    eng = PagedEngine(
        model, max_batch=3, block_size=4,
        scheduler=(SchedulerConfig(prefill_token_budget=budget)
                   if budget else None), **kw)
    assert eng.prefill_width == width
    return eng


def _patterned(vocab, n, seed):
    """A prompt of a tiled 5-token pattern, so the n-gram proposer has
    something to propose."""
    pat = np.random.RandomState(seed).randint(1, vocab, size=5)
    return [int(t) for t in np.resize(pat, n)]


class TestPrefillShape:
    # prompt lengths 1, W-1, W, W+1 of the widest engine's W = 12, and one
    # of several chunks at every width
    @pytest.mark.parametrize("n_prompt", [1, 11, 12, 13, 29])
    @pytest.mark.parametrize("speculate", [None, "ngram"])
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("arch", ["llama", "gpt"])
    def test_greedy_tokens_equal_at_every_width(self, arch, kv_dtype,
                                                speculate, n_prompt):
        model = _tiny_model() if arch == "llama" else _tiny_gpt()
        prompt = _patterned(model.cfg.vocab_size, n_prompt, seed=n_prompt)
        got = {}
        for kind in _WIDTHS:
            eng = _width_engine(model, kind, kv_dtype=kv_dtype,
                                speculate=speculate)
            rid = eng.add_request(prompt, max_new_tokens=6)
            got[kind] = eng.run_to_completion()[rid]
            assert eng.tick_failures == 0
        assert got["wide"] == got["block"]
        assert got["budget"] == got["block"]
        if kv_dtype is None:
            # the float engine is also the model's own greedy decode
            assert_greedy(model, prompt, got["block"], 6)

    @pytest.mark.parametrize("kind", list(_WIDTHS))
    def test_preemption_reprefills_same_tokens(self, kind):
        """A preempted request re-prefills prompt + generated through the
        same (1, W) program and ends on the model's greedy tokens."""
        model = _tiny_model()
        rng = np.random.RandomState(4)
        prompts = [[int(t) for t in rng.randint(1, 97, size=4)]
                   for _ in range(2)]
        eng = _width_engine(model, kind, num_blocks=5, max_blocks_per_seq=4)
        evicted = watch_evictions(eng)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out = eng.run_to_completion(max_ticks=200)
        assert evicted
        for rid, p in zip(rids, prompts):
            assert_greedy(model, p, out[rid], 6)
        assert eng.bm.available == 4

    @pytest.mark.parametrize("kind", list(_WIDTHS))
    @pytest.mark.parametrize("arch", ["llama", "gpt"])
    def test_one_prefill_shape_compiled_by_warmup(self, arch, kind):
        """The chunk call is (1, W) with the slot's own block table, and
        after warmup() a prompt of any length compiles nothing."""
        # a model of its own: the programs of a shared one are shared too
        paddle.seed(3)
        if arch == "llama":
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=61, hidden_size=32, intermediate_size=64,
                num_layers=1, num_heads=2, max_seq_len=64,
                use_flash_attention=False))
        else:
            model = GPTForCausalLM(GPTConfig(
                vocab_size=61, hidden_size=32, num_layers=1, num_heads=2,
                max_seq_len=64, dropout=0.0, use_flash_attention=False))
        eng = _width_engine(model, kind)
        width = eng.prefill_width
        prefill, shapes = eng._fns["prefill"], []

        def recording(*args, **kw):
            shapes.append(tuple(a.shape for a in args[3:10]))
            return prefill(*args, **kw)

        eng._fns["prefill"] = recording
        eng.warmup()
        compiled = lambda: (prefill._cache_size(),
                            eng._fns["decode"]._cache_size())
        assert compiled() == (1, 1)
        for n in (1, width - 1, width, width + 1, 3 * width + 2):
            eng.add_request(list(range(1, n + 1)), max_new_tokens=3)
        eng.run_to_completion()
        assert compiled() == (1, 1)
        # tokens, seq, tables, temps, top_ps, rids, ngens: one lane each
        assert set(shapes) == {((1, width), (1,), (1, 16), (1,), (1,),
                                (1,), (1,))}

    @pytest.mark.parametrize("n_prompt,kind,rows", [
        (10, "wide", 12), (13, "wide", 24), (10, "block", 12),
        (3, "budget", 8), (16, "budget", 16)])
    def test_prefill_fill_is_real_over_computed(self, n_prompt, kind, rows):
        from paddle_tpu.observability import REGISTRY
        paddle.set_flags({"FLAGS_enable_metrics": True})
        try:
            REGISTRY.reset()
            eng = _width_engine(_tiny_model(), kind)
            assert eng.health()["prefill_fill"] is None
            eng.add_request(list(range(1, n_prompt + 1)), max_new_tokens=2)
            eng.run_to_completion()
            sched = eng.scheduler
            assert sched.prefill_prompt_tokens == n_prompt
            assert sched.prefill_tokens == rows
            assert eng.health()["prefill_fill"] == n_prompt / rows
            assert REGISTRY.get(
                "paddle_tpu_serving_prefill_prompt_tokens_total"
            ).total() == n_prompt
            assert REGISTRY.get(
                "paddle_tpu_serving_prefill_tokens_total").total() == rows
        finally:
            paddle.set_flags({"FLAGS_enable_metrics": False})
            REGISTRY.reset()


class TestDecodeKernelParity:
    """The decode program with the Pallas decode kernel (through the
    interpreter) serves what it serves with the composite."""

    @staticmethod
    def _model():
        # a model of its own a run: the compiled programs of a shared one
        # are shared too, and the two runs must trace their own. Heads of
        # 128 and pages of 16 tokens x 2 KV heads: shapes the kernel takes
        paddle.seed(5)
        cfg = LlamaConfig(vocab_size=97, hidden_size=1024,
                          intermediate_size=128, num_layers=2, num_heads=8,
                          num_kv_heads=2, max_seq_len=128,
                          use_flash_attention=False)
        return LlamaForCausalLM(cfg)

    def _serve(self, prompts):
        """Three lanes, a 16-token prefill budget (a long prompt is
        mid-prefill over several ticks while the others decode) and a pool
        too small for all of them (one is preempted and re-prefilled)."""
        eng = PagedEngine(
            self._model(), max_batch=3, block_size=16, num_blocks=8,
            max_blocks_per_seq=6,
            scheduler=SchedulerConfig(prefill_token_budget=16))
        evicted, sentinel_ticks = watch_evictions(eng), []
        run = eng._run_chunk

        def spy(rec, tokens, seq_lens, *a, **kw):
            if rec.phase == "decode" and eng._prefilling:
                sentinel_ticks.append((np.asarray(seq_lens) <= 0).sum())
            return run(rec, tokens, seq_lens, *a, **kw)

        eng._run_chunk = spy
        rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
        out = eng.run_to_completion(max_ticks=400)
        assert eng.tick_failures == 0
        assert evicted, "no lane was preempted"
        assert any(sentinel_ticks), "no decode step ran beside a prefill"
        return [out[r] for r in rids], eng.health()["decode_attention"]

    def test_same_greedy_tokens_with_kernel_and_composite(self, monkeypatch):
        from paddle_tpu.ops.pallas import paged_attention as PK

        rng = np.random.RandomState(6)
        prompts = [[int(t) for t in rng.randint(1, 97, size=n)]
                   for n in (14, 40, 30)]
        want, path = self._serve(prompts)
        assert path == "composite"
        monkeypatch.setattr(PK, "INTERPRET", True)
        got, path = self._serve(prompts)
        assert path == "kernel"
        assert got == want

    @pytest.mark.parametrize("kw,path", [
        (dict(), "kernel"),
        (dict(kv_dtype="int8"), "composite"),
        (dict(speculate="ngram"), "composite"),
        (dict(block_size=2), "composite"),
        # the tiny shared model's heads are 16 wide: never the kernel
        (dict(model=_tiny_model), "composite")])
    def test_health_says_what_the_decode_program_was_lowered_to(
            self, monkeypatch, kw, path):
        from paddle_tpu.ops.pallas import paged_attention as PK

        monkeypatch.setattr(PK, "INTERPRET", True)
        geometry = dict(max_batch=2, block_size=16, num_blocks=8,
                        max_blocks_per_seq=4)
        fresh = "model" not in kw
        model = kw.pop("model", self._model)()
        eng = PagedEngine(model, **{**geometry, **kw})
        if fresh:   # no program yet (the shared model may bring its own)
            assert eng.health()["decode_attention"] is None
        eng.add_request([3, 1, 4], max_new_tokens=3)
        eng.run_to_completion(max_ticks=20)
        assert eng.health()["decode_attention"] == path
        # the gauge is the compiled program's, not the rule's of the moment;
        # a second engine over the model shares program and gauge
        monkeypatch.setattr(PK, "INTERPRET", False)
        assert eng.health()["decode_attention"] == path
        twin = PagedEngine(model, **{**geometry, **kw})
        assert twin.health()["decode_attention"] == path


class TestCacheLayout:
    """What a layer keeps follows from the model's ``cache_layout``; a model
    without one keeps pages in every layer."""

    def test_a_dense_decoder_keeps_pages_and_nothing_else(self):
        from paddle_tpu.inference import resilience, serving
        eng = PagedEngine(_tiny_model(), max_batch=2, block_size=8,
                          num_blocks=16, max_blocks_per_seq=4)
        assert eng._cache_index == {(0, "paged_kv"): 0, (1, "paged_kv"): 1}
        h = eng.health()
        assert h["window_bytes_per_slot"] == h["state_bytes_per_slot"] == 0
        assert resilience.M_WINDOW_BYTES.value() == 0
        # both layers page: K and V of 4 heads of 16 in float32, twice
        assert h["kv_bytes_per_token"] == 2 * 2 * 4 * 16 * 4
        assert eng.state == [] and len(eng.kc) == 2
        assert list(serving._layer_states(
            [None, ("paged_kv",), (("window_kv", 8), ("accumulator", (3,),
                                                      "int32"))])) == [
            (1, ("paged_kv",)), (2, ("window_kv", 8)),
            (2, ("accumulator", (3,), "int32"))]

    def test_a_prefill_chunk_touches_its_own_slots_rows_only(self):
        """The cache handle with ``lanes``: a chunk of slot 2 writes slot
        2's window rows; slots 0, 1 and 3 keep theirs bit for bit."""
        import jax.numpy as jnp
        from paddle_tpu.inference import serving
        rng = np.random.RandomState(0)
        rows = jnp.asarray(rng.randn(4, 24, 2, 16).astype(np.float32))
        T = 16
        q = paddle.to_tensor(rng.randn(1, T, 4, 16).astype(np.float32))
        k = paddle.to_tensor(rng.randn(1, T, 2, 16).astype(np.float32))
        start = jnp.asarray([16], jnp.int32)        # the second chunk
        cache = serving._PagedCache(
            {(0, "window_kv"): 0}, [], [], [{"k": rows, "v": rows}],
            jnp.zeros((1, 4), jnp.int32), start + T, start,
            jnp.asarray([2], jnp.int32), T)
        out = cache.attend(0, q, k, k, window=8)
        assert out.shape == [1, T, 4, 16]
        got = np.asarray(cache.states[0]["k"])
        for slot in (0, 1, 3):
            np.testing.assert_array_equal(got[slot], np.asarray(rows)[slot])
        # positions 16..31 live in rows 16..23 and 0..7 of a ring of 24
        np.testing.assert_array_equal(got[2, 16:], k.numpy()[0, :8])
        np.testing.assert_array_equal(got[2, :8], k.numpy()[0, 8:])
        np.testing.assert_array_equal(got[2, 8:16], np.asarray(rows)[2, 8:16])


def test_compiled_programs_do_not_keep_a_model_alive():
    """Engines of one model share compiled programs through a table keyed
    weakly by the model; the programs reach the model through a proxy, so
    once the engine and the caller let go, the model and its parameters'
    arrays go too (a benchmark run frees the program before it builds the
    float32 reference)."""
    import gc
    import weakref
    paddle.seed(3)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=61, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=2, max_seq_len=32, use_flash_attention=False))
    eng = PagedEngine(model, max_batch=2, block_size=8, num_blocks=8,
                      max_blocks_per_seq=2)
    rid = eng.add_request([1, 2, 3], max_new_tokens=2)
    assert len(eng.run_to_completion()[rid]) == 2
    twin = PagedEngine(model, max_batch=2, block_size=8, num_blocks=8,
                       max_blocks_per_seq=2)
    assert twin._fns["decode"] is eng._fns["decode"]    # still shared
    held = [weakref.ref(model), weakref.ref(eng),
            weakref.ref(next(iter(model.parameters()))._data)]
    del model, eng, twin
    gc.collect()
    assert [ref() for ref in held] == [None, None, None]


class TestLatentPages:
    """A latent-attention decoder through the engine: ``latent_kv`` is the
    fifth state kind, one pool a layer under the one block table."""

    @staticmethod
    def _model():
        from paddle_tpu.models import DeepseekV3ForCausalLM, deepseek_v3_tiny
        paddle.seed(11)
        m = DeepseekV3ForCausalLM(deepseek_v3_tiny(vocab_size=97))
        m.eval()
        return m

    def test_state_kinds_and_the_handles_write(self):
        """``_STATE_KINDS`` names five kinds; the cache handle pads queries
        and rows to the pool's lane tiles, writes the chunk's rows where
        the table says and nowhere else, and hands back values
        ``value_dim`` wide."""
        import jax.numpy as jnp
        from paddle_tpu.inference import serving
        assert serving._STATE_KINDS == ("paged_kv", "latent_kv", "window_kv",
                                        "slot_state", "accumulator")
        assert list(serving._layer_states(
            [("latent_kv", 40), (("latent_kv", 40), ("accumulator", (3,),
                                                     "int32"))])) == [
            (0, ("latent_kv", 40)), (1, ("latent_kv", 40)),
            (1, ("accumulator", (3,), "int32"))]
        rng = np.random.RandomState(0)
        pool = jnp.asarray(rng.randn(6, 8, 128).astype(np.float32))
        T = 8
        q = paddle.to_tensor(rng.randn(1, T, 4, 40).astype(np.float32))
        rows = paddle.to_tensor(rng.randn(1, T, 40).astype(np.float32))
        start = jnp.asarray([8], jnp.int32)         # the second chunk
        tables = jnp.asarray([[3, 5, 1, 0]], jnp.int32)
        cache = serving._PagedCache(
            {(0, "latent_kv"): 0}, [], [], [pool], tables, start + T, start,
            None, T)
        out = cache.attend_latent(0, q, rows, 32, 0.2)
        assert out.shape == [1, T, 4, 32]
        got = np.asarray(cache.states[0])
        # positions 8..15 live in the table's second block, 5
        np.testing.assert_array_equal(got[5, :, :40], rows.numpy()[0])
        np.testing.assert_array_equal(got[5, :, 40:], 0.0)
        for blk in (0, 1, 2, 3, 4):
            np.testing.assert_array_equal(got[blk], np.asarray(pool)[blk])

    def test_preempted_and_readmitted_tokens_are_the_undisturbed_ones(self):
        """Memory for one long request at a time: lanes stall, one is
        preempted (its latent pages freed, as K/V pages are) and re-prefilled
        later over prompt + generated tokens; greedy tokens are those of an
        engine with room for all."""
        model = self._model()
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, 97, n).tolist() for n in (12, 12, 9)]

        def serve(**kw):
            eng = PagedEngine(model, max_batch=4, block_size=8,
                              max_blocks_per_seq=6, **kw)
            evicted = watch_evictions(eng)
            rids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
            out = eng.run_to_completion(max_ticks=600)
            assert eng.bm.available == eng._total_usable
            return [out[r] for r in rids], evicted

        want, none = serve(num_blocks=32)
        got, evicted = serve(num_blocks=9)
        assert not none and evicted
        assert got == want

    def test_decode_takes_the_kernel_and_health_counts_the_latent_call(
            self, monkeypatch):
        """With the kernel available (the interpreter, here) the decode
        program of a model whose rows fill lane tiles attends through
        ``paged_decode_attn`` in every layer, the path gauge says so, and
        the tokens are the composite's."""
        from paddle_tpu.models import DeepseekV3ForCausalLM, deepseek_v3_tiny
        from paddle_tpu.ops.pallas import paged_attention as PK

        def fresh():
            paddle.seed(12)
            m = DeepseekV3ForCausalLM(deepseek_v3_tiny(
                vocab_size=97, num_attention_heads=8, kv_lora_rank=128,
                qk_rope_head_dim=64))
            m.eval()
            return m

        geometry = dict(max_batch=2, block_size=8, num_blocks=24,
                        max_blocks_per_seq=6)
        prompts = [[5, 9, 33, 2, 71, 8, 14], [3, 1, 4, 1, 5]]

        def serve(eng):
            rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
            out = eng.run_to_completion()
            return [out[r] for r in rids]

        plain = PagedEngine(fresh(), **geometry)
        want = serve(plain)
        assert plain.health()["decode_attention"] == "composite"
        monkeypatch.setattr(PK, "INTERPRET", True)
        eng = PagedEngine(fresh(), **geometry)
        assert serve(eng) == want
        assert eng.health()["decode_attention"] == "kernel"
        assert eng.health()["latent_bytes"] == 3 * 24 * 8 * 256 * 4


class TestMatrixSlotState:
    """A state of megabytes a lane (a delta-rule matrix a head) under
    ``slot_state``: the handle can leave a fresh lane's zeroing and an idle
    lane's keeping to the function that visits the state."""

    @pytest.mark.parametrize("lanes", [None, [5, 127, 0]],
                             ids=["every-lane", "three-of-128"])
    @pytest.mark.parametrize("masks", [False, True])
    def test_recur_hands_fresh_and_idle_to_the_function_at_128_lanes(
            self, lanes, masks):
        """``recur(..., masks=True)``: ``fn`` gets the lanes' states as they
        lie (no ``where`` over them before or after) and (lanes,) ``fresh``
        / ``idle`` flags, and what it returns is written as it is; without
        ``masks`` the handle applies both rules itself. 128 slots: the
        per-lane slices and writes at a batch no cell ran before."""
        import jax.numpy as jnp
        from paddle_tpu.inference import serving
        slots = 128
        state = {"s": jnp.arange(slots * 6, dtype=jnp.float32).reshape(
            slots, 2, 3) + 1.0}
        mine = list(range(slots)) if lanes is None else lanes
        rows = len(mine)
        # lane 0 of the group idle (seq = 0), lane 1 first token, rest later
        start = jnp.asarray(([-1, 0] + [5] * rows)[:rows], jnp.int32)
        cache = serving._PagedCache(
            {0: ("slot_state", 0)}, [], [], [dict(state)],
            jnp.zeros((rows, 1), jnp.int32), start + 1, start,
            None if lanes is None else jnp.asarray(lanes, jnp.int32), 1)
        seen = {}

        def fn(st, *flags):
            seen["in"], seen["flags"] = st["s"], flags
            return jnp.zeros(()), {"s": st["s"] + 100.0}

        cache.recur(0, fn, masks=masks)
        got = np.asarray(cache.states[0]["s"])
        before = np.asarray(state["s"])
        if masks:
            fresh, idle = (np.asarray(f) for f in seen["flags"])
            # the sentinel lane is "fresh" too (start -1): fn sorts it out
            assert fresh.tolist() == ([True, True] + [False] * rows)[:rows]
            assert idle.tolist() == ([True] + [False] * rows)[:rows]
            assert (np.asarray(seen["in"]) == before[mine]).all()
            assert (got[mine] == before[mine] + 100.0).all()
        else:
            assert seen["flags"] == ()
            assert (got[mine[0]] == before[mine[0]]).all()          # kept
            assert (np.asarray(seen["in"])[1] == 0).all()           # reset
            assert (got[mine[1]] == 100.0).all()
            assert (got[mine[2:]] == before[mine[2:]] + 100.0).all()
        others = sorted(set(range(slots)) - set(mine))
        assert (got[others] == before[others]).all()                # no lane

    def test_speculation_is_refused_naming_the_state_kind(self):
        from paddle_tpu.models import OlmoHybridForCausalLM, olmo_hybrid_tiny
        paddle.seed(3)
        m = OlmoHybridForCausalLM(olmo_hybrid_tiny(num_hidden_layers=4))
        with pytest.raises(TypeError, match="slot_state"):
            PagedEngine(m, max_batch=2, block_size=8, num_blocks=16,
                        max_blocks_per_seq=4, speculate="ngram")

    def test_state_bytes_count_the_packed_matrix_and_the_window(self):
        from paddle_tpu.models import OlmoHybridForCausalLM, olmo_hybrid_tiny
        paddle.seed(3)
        m = OlmoHybridForCausalLM(olmo_hybrid_tiny(num_hidden_layers=4))
        m.eval()
        eng = PagedEngine(m, max_batch=128, block_size=8, num_blocks=16,
                          max_blocks_per_seq=4)
        # three linear layers: a 3 x 480 window and six 8 x 64 float32
        # matrices packed (3, 8, 128); one full layer: K and V of 16 heads
        # (6 padded to a tile) of 16
        assert eng.state_bytes_per_slot == 3 * (3 * 480 + 6 * 8 * 64) * 4
        h = eng.health()
        assert h["state_bytes_per_slot"] == eng.state_bytes_per_slot
        assert h["kv_bytes_per_token"] == 2 * 16 * 16 * 4
        assert [tuple(st["s"].shape) for st in eng.state] == \
            [(128, 3, 8, 128)] * 3
