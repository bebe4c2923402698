"""Serving-tier drills: router fault handling, phase-split scheduling,
int8-KV / speculative parity, streaming, and sampling determinism.

Contract under test (ISSUE 13 / README "Serving tier"):

* the Router fronts R replicas keyed on the round-11 readiness probes —
  a replica DEGRADED mid-flight strands nothing (requests re-route with
  their paid-for tokens carried), an all-saturated tier sheds AT THE
  ROUTER (replicas never see the burst), a drain mid-stream terminates
  the stream with a terminal status and leaks zero KV blocks;
* ``kv_dtype="int8"`` and ``speculate="ngram"`` are parity-gated:
  greedy outputs identical to the baseline decode path;
* the phase-split scheduler interleaves chunked prefill with decode
  without changing tokens;
* sampled decoding is per-request deterministic: a preempt-then-resume
  run emits exactly the tokens of an unpreempted run under a fixed seed.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.fault import inject
from paddle_tpu.inference import (PagedEngine, ReplicaState, RequestStatus,
                                  ResilienceConfig)
from paddle_tpu.inference.resilience import TERMINAL_STATUSES
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (NgramProposer, Router, SchedulerConfig,
                                TokenStream)


@pytest.fixture(scope="module")
def model():
    # 1 layer on purpose: this suite compiles MANY distinct programs
    # (fp + int8 caches, chunk + decode + verify, reference forwards) —
    # every serving behavior under test is layer-count independent, and
    # test_serving.py keeps the 2-layer decode-parity coverage
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=97, hidden_size=48, intermediate_size=96,
                      num_layers=1, num_heads=4, max_seq_len=256,
                      use_flash_attention=False)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _clean_faults():
    inject.disarm_all()
    yield
    inject.disarm_all()


def make_engine(model, *, max_batch=2, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, res=None, **eng_kw):
    return PagedEngine(model, max_batch=max_batch, block_size=block_size,
                       num_blocks=num_blocks,
                       max_blocks_per_seq=max_blocks_per_seq,
                       resilience=res, **eng_kw)


def prompt(seed, n=5):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(1, 97, size=n)]


def ref_greedy(model, p, n_new):
    """Reference completion from a plain single-replica engine — the
    anchor for 'nothing lost / tokens identical' drills. (Engine-vs-
    model.generate parity is test_serving.py's job; reusing the engine
    here keeps every reference on the file's already-compiled tick
    programs instead of one full-recompute forward per length.)"""
    eng = make_engine(model)
    rid = eng.add_request(p, max_new_tokens=n_new)
    return eng.run_to_completion()[rid]


def assert_no_leaks(replicas):
    for rep in replicas:
        assert rep.bm.available == rep._total_usable, \
            f"{rep.lifecycle.name} leaked KV blocks"
        assert all(s is None for s in rep.slots)


# ------------------------------------------------------------ router drills
class TestRouterRouting:
    def test_balances_and_finishes_across_replicas(self, model):
        router = Router([make_engine(model) for _ in range(2)]).warmup()
        prompts = [prompt(i, n=4 + i) for i in range(6)]
        rids = [router.add_request(p, max_new_tokens=5) for p in prompts]
        router.run_to_completion()
        ocs = router.drain_outcomes()
        for rid, p in zip(rids, prompts):
            assert ocs[rid].status == RequestStatus.FINISHED
            assert ocs[rid].tokens == ref_greedy(model, p, 5)
        stats = router.stats()
        assert all(r["routed"] > 0 for r in stats["per_replica"])
        assert_no_leaks(router.replicas)

    def test_not_ready_replica_out_of_rotation(self, model):
        a, b = make_engine(model), make_engine(model)
        router = Router([a, b]).warmup()
        a.lifecycle.degrade("drill")
        rids = [router.add_request(prompt(i), max_new_tokens=3)
                for i in range(3)]
        router.run_to_completion()
        ocs = router.drain_outcomes()
        assert all(ocs[r].status == RequestStatus.FINISHED for r in rids)
        assert router.stats()["per_replica"][0]["routed"] == 0
        assert router.stats()["per_replica"][1]["routed"] == 3

    def test_degraded_mid_flight_reroutes_nothing_lost(self, model):
        """The headline drill: a replica tick-crashes with requests in
        flight; the router re-routes them (generated prefix carried) and
        the client-visible outcome is the SAME greedy completion."""
        router = Router([make_engine(model) for _ in range(2)]).warmup()
        p = prompt(3, n=6)
        rid = router.add_request(p, max_new_tokens=8)
        router.step()                       # admitted + first tokens
        rr = router._by_rid[rid]
        assert rr.replica_idx is not None
        victim = router.replicas[rr.replica_idx]
        with inject.armed("serving.crash_at_tick",
                          tick=victim._ticks + 1):
            router.run_to_completion()
        oc = router.drain_outcomes()[rid]
        assert oc.status == RequestStatus.FINISHED
        assert oc.tokens == ref_greedy(model, p, 8)
        assert victim.lifecycle.state == ReplicaState.DEGRADED
        stats = router.stats()
        assert sum(r["rerouted_away"] for r in stats["per_replica"]) >= 1
        assert_no_leaks(router.replicas)

    def test_stream_attached_after_reroute_replays_carried_tokens(
            self, model):
        """A stream opened (or read) after a re-route must replay the
        tokens generated on the failed replica — the hand-off is
        invisible in the stream, not a gap."""
        router = Router([make_engine(model) for _ in range(2)]).warmup()
        p = prompt(8, n=6)
        rid = router.add_request(p, max_new_tokens=8)
        router.step()                       # some tokens on replica A
        victim = router.replicas[router._by_rid[rid].replica_idx]
        with inject.armed("serving.crash_at_tick",
                          tick=victim._ticks + 1):
            router.step()                   # crash + re-route
        toks = list(router.stream(rid))     # attached AFTER the crash
        assert toks == ref_greedy(model, p, 8)

    def test_all_overloaded_sheds_at_router_not_in_replicas(self, model):
        """Saturate every replica's bounded queue, then burst: the burst
        becomes router-level SHED outcomes; replicas never see it (no
        replica-side sheds, queues never exceed their bound)."""
        reps = [make_engine(model, res=ResilienceConfig(max_queue=2))
                for _ in range(2)]
        router = Router(reps).warmup()
        # fill both admission queues to their bound (nothing ticks in
        # between, so 2 queued per replica saturates the tier)
        fill = [router.add_request(prompt(10 + i), max_new_tokens=4)
                for i in range(4)]
        routed_before = [r["routed"] for r in
                         router.stats()["per_replica"]]
        burst = [router.add_request(prompt(50 + i), max_new_tokens=4)
                 for i in range(5)]
        ocs = {rid: router.outcomes[rid] for rid in burst}
        assert all(oc.status == RequestStatus.SHED for oc in ocs.values())
        assert all("router" in oc.detail for oc in ocs.values())
        assert router.shed_at_router == 5
        # replicas never saw the burst: routed counters unchanged, and
        # no replica-side shed ever happened
        assert [r["routed"] for r in
                router.stats()["per_replica"]] == routed_before
        router.run_to_completion()
        ocs = router.drain_outcomes()
        for rid in fill:
            assert ocs[rid].status == RequestStatus.FINISHED
        for rep in reps:
            assert not any(
                oc.status == RequestStatus.SHED
                for oc in rep.outcomes.values())
        assert_no_leaks(reps)

    def test_drain_during_streaming_terminates_with_status(self, model):
        """Replica drained while a client streams from it: the stream
        ends (no hang, no raise) with a terminal status, and no replica
        leaks KV blocks."""
        reps = [make_engine(model) for _ in range(2)]
        router = Router(reps).warmup()
        p = prompt(4, n=6)
        rid = router.add_request(p, max_new_tokens=8)
        stream = router.stream(rid)
        first = next(stream)               # pumps until a token arrives
        serving_rep = router.replicas[router._by_rid[rid].replica_idx]
        serving_rep.drain()                # finishes in-flight decodes
        rest = list(stream)
        assert stream.status in TERMINAL_STATUSES
        assert stream.status == RequestStatus.FINISHED
        assert [first] + rest == ref_greedy(model, p, 8)
        assert serving_rep.lifecycle.state == ReplicaState.STOPPED
        assert_no_leaks(reps)

    def test_drain_before_admission_reroutes_queued_request(self, model):
        """A drain cancels queued requests 'their clients retry on
        another replica' — the router IS that client: the queued request
        re-routes and still finishes with the right tokens."""
        reps = [make_engine(model) for _ in range(2)]
        router = Router(reps).warmup()
        p1, p2, p3 = prompt(5), prompt(6), prompt(7)
        # aim all at replica 0 by degrading replica 1 momentarily
        reps[1].lifecycle.degrade("hold")
        r1 = router.add_request(p1, max_new_tokens=6)
        r2 = router.add_request(p2, max_new_tokens=6)
        r3 = router.add_request(p3, max_new_tokens=6)
        reps[1].recover()
        assert router._by_rid[r3].replica_idx == 0   # queued behind r1/r2
        reps[0].drain()          # r1/r2 finish, queued r3 CANCELLED
        router.run_to_completion()
        ocs = router.drain_outcomes()
        assert ocs[r1].status == RequestStatus.FINISHED
        assert ocs[r3].status == RequestStatus.FINISHED
        assert ocs[r3].tokens == ref_greedy(model, p3, 6)
        # the drained-before-admission request was re-routed
        assert router.stats()["per_replica"][0]["rerouted_away"] >= 1
        assert_no_leaks(reps)

    def test_router_drain_terminates_everything(self, model):
        router = Router([make_engine(model) for _ in range(2)]).warmup()
        rids = [router.add_request(prompt(20 + i), max_new_tokens=6)
                for i in range(5)]
        router.step()
        router.drain()
        ocs = router.drain_outcomes()
        for rid in rids:
            assert ocs[rid].status in TERMINAL_STATUSES
        assert_no_leaks(router.replicas)


# --------------------------------------------------- int8 / speculative
class TestQuantizedKVParity:
    def test_int8_greedy_identical_and_smaller(self, model):
        prompts = [prompt(i, n=n) for i, n in enumerate((11, 23, 5, 17))]
        base = make_engine(model)
        eng8 = make_engine(model, kv_dtype="int8")
        b_rids = [base.add_request(p, max_new_tokens=10) for p in prompts]
        q_rids = [eng8.add_request(p, max_new_tokens=10) for p in prompts]
        b_out = base.run_to_completion()
        q_out = eng8.run_to_completion()
        for br, qr in zip(b_rids, q_rids):
            assert q_out[qr] == b_out[br]
        # resident KV per token shrinks (payload int8 + fp32 scales
        # vs the model dtype pages): the resident-batch multiplier
        assert eng8.kv_bytes_per_token < base.kv_bytes_per_token
        assert eng8.health()["kv_dtype"] == "int8"

    def test_int8_survives_preemption_and_growth(self, model):
        # tight blocks: eviction + re-prefill exercise quantized rewrite
        p1, p2 = prompt(30, n=4), prompt(31, n=4)
        eng = make_engine(model, num_blocks=5, max_blocks_per_seq=4,
                          kv_dtype="int8")
        r1 = eng.add_request(p1, max_new_tokens=6)
        r2 = eng.add_request(p2, max_new_tokens=6)
        out = eng.run_to_completion(max_ticks=200)
        assert out[r1] == ref_greedy(model, p1, 6)
        assert out[r2] == ref_greedy(model, p2, 6)


class TestSpeculativeDecode:
    def test_ngram_proposer_finds_repeats(self):
        prop = NgramProposer(k=3, max_n=3)
        # trailing (7, 8) occurred earlier, followed by 9, 1, 2
        assert prop.propose([7, 8, 9, 1, 2, 7, 8]) == [9, 1, 2]
        assert prop.propose([1, 2, 3]) == []       # no repeat, no draft

    def test_spec_greedy_identical_with_acceptance(self, model):
        # repetitive prompts so the n-gram draft actually accepts
        prompts = [p * 3 for p in
                   (prompt(40, n=4), prompt(41, n=6), prompt(42, n=3))]
        base = make_engine(model)
        spec = make_engine(model, speculate="ngram", speculate_k=4)
        b_rids = [base.add_request(p, max_new_tokens=12) for p in prompts]
        s_rids = [spec.add_request(p, max_new_tokens=12) for p in prompts]
        b_out = base.run_to_completion()
        s_out = spec.run_to_completion()
        for br, sr in zip(b_rids, s_rids):
            assert s_out[sr] == b_out[br]
        assert spec.spec_proposed > 0
        assert spec.health()["spec_acceptance_rate"] is not None

    @staticmethod
    def _draftable_prompt(model, n_new=12, k=4):
        """A prompt whose greedy continuation the n-gram proposer can
        draft, derived from the model's OWN greedy decode (this tiny
        model keeps replaying earlier n-grams) instead of being pinned
        to one RNG stream's weights."""
        prop = NgramProposer(k=k)
        for seed in range(8):
            seed_p = prompt(seed, n=3)
            full = seed_p + ref_greedy(model, seed_p, 36 + n_new)
            ctx, cont = full[:-n_new], full[-n_new:]
            hits = sum((prop.propose(ctx + cont[:i]) or [None])[0]
                       == cont[i] for i in range(n_new))
            if hits >= n_new // 2:
                return ctx
        pytest.fail("no seed prompt led this model into a repetitive "
                    "greedy continuation")

    def test_spec_saves_ticks_on_repetitive_text(self, model):
        # the continuation replays n-grams of the prompt: acceptance
        # must compress ticks
        p = self._draftable_prompt(model)
        base = make_engine(model)
        spec = make_engine(model, speculate="ngram", speculate_k=4)
        rb = base.add_request(p, max_new_tokens=12)
        rs = spec.add_request(p, max_new_tokens=12)
        assert base.run_to_completion()[rb] == \
            spec.run_to_completion()[rs]
        assert spec._ticks < base._ticks
        assert spec.spec_accepted > 0

    def test_spec_sampling_slots_match_plain_sampling(self, model):
        # temperature>0 slots ride the verify program with acceptance
        # disabled — tokens must equal the plain decode path's sampling
        p = prompt(43, n=6)

        def run(**kw):
            eng = make_engine(model, seed=11, **kw)
            rid = eng.add_request(p, max_new_tokens=8, temperature=0.9,
                                  top_p=0.9)
            return eng.run_to_completion()[rid]

        assert run() == run(speculate="ngram", speculate_k=4)

    def test_spec_near_block_table_capacity_falls_back(self, model):
        """A sequence within k of its max_blocks_per_seq ceiling must
        not feed a (seq+k) verify (block-table lookups would clamp into
        a foreign block); the engine decodes plainly through the
        boundary instead of crashing the tick."""
        # cap = 4 blocks * 4 = 16 positions; prompt 8 + 8 new == cap
        p = prompt(45, n=8)
        base = make_engine(model, num_blocks=64, max_blocks_per_seq=4)
        spec = make_engine(model, num_blocks=64, max_blocks_per_seq=4,
                           speculate="ngram", speculate_k=4)
        rb = base.add_request(p, max_new_tokens=8)
        rs = spec.add_request(p, max_new_tokens=8)
        b = base.run_to_completion()
        s = spec.run_to_completion()
        assert spec.tick_failures == 0
        assert spec.lifecycle.state != ReplicaState.DEGRADED
        assert s[rs] == b[rb]

    def test_spec_with_eos_stops_exactly(self, model):
        p = prompt(44, n=5)
        base = make_engine(model)
        rb = base.add_request(p, max_new_tokens=10)
        b_toks = base.run_to_completion()[rb]
        eos = b_toks[3]
        base2 = make_engine(model, eos_id=eos)
        spec = make_engine(model, eos_id=eos, speculate="ngram")
        r2 = base2.add_request(p, max_new_tokens=10)
        r3 = spec.add_request(p, max_new_tokens=10)
        assert base2.run_to_completion()[r2] == \
            spec.run_to_completion()[r3]


# ------------------------------------------------- phase-split scheduler
class TestPhaseSplitScheduler:
    def test_budgeted_prefill_same_tokens(self, model):
        long_p = prompt(50, n=40)
        short_p = prompt(51, n=4)
        base = make_engine(model, max_batch=2)
        split = make_engine(
            model, max_batch=2,
            scheduler=SchedulerConfig(prefill_token_budget=4))
        b1 = base.add_request(long_p, max_new_tokens=6)
        b2 = base.add_request(short_p, max_new_tokens=6)
        s1 = split.add_request(long_p, max_new_tokens=6)
        s2 = split.add_request(short_p, max_new_tokens=6)
        b_out = base.run_to_completion()
        s_out = split.run_to_completion()
        assert s_out[s1] == b_out[b1]
        assert s_out[s2] == b_out[b2]
        # the budget actually deferred chunks across ticks
        assert split.scheduler.deferred_chunks > 0
        assert split._ticks > base._ticks

    def test_decode_not_starved_by_long_prompt(self, model):
        """Decode-priority: while a 40-token prompt trickles through a
        4-token/tick budget, the already-running request keeps emitting
        a token EVERY tick."""
        split = make_engine(
            model, max_batch=2,
            scheduler=SchedulerConfig(prefill_token_budget=4))
        fast = split.add_request(prompt(52, n=4), max_new_tokens=30)
        split.step()                        # fast prefilled + 1 token
        split.add_request(prompt(53, n=40), max_new_tokens=4)
        split.step()                        # long admitted, chunk 1 of 10
        assert 1 in split._prefilling
        n0 = len(split.slots[0].generated)
        ticks = 0
        while 1 in split._prefilling and ticks < 50:
            split.step()
            ticks += 1
        assert ticks > 1                    # prompt really was chunked
        fast_req = split.slots[0]
        assert fast_req is not None and fast_req.rid == fast
        # decode never starved: one token EVERY tick of the prefill
        assert len(fast_req.generated) == n0 + ticks
        assert split.scheduler.phase_share()["prefill"] is not None
        split.drain()

    # the tick's tokens: one chunk as wide as the budget (4, 8: W = 4, 8),
    # a budget under one block still moves a block, and a budget wider
    # than the engine's widest chunk (2 lanes x 4 = 8) buys whole chunks
    @pytest.mark.parametrize("budget,width,per_tick", [
        (4, 4, 4), (8, 8, 8), (2, 4, 4), (20, 8, 16)])
    def test_no_tick_over_budget_and_decode_every_tick(
            self, model, budget, width, per_tick):
        split = make_engine(
            model, max_batch=2,
            scheduler=SchedulerConfig(prefill_token_budget=budget))
        assert split.prefill_width == width
        fast = split.add_request(prompt(55, n=4), max_new_tokens=40)
        split.step()
        split.add_request(prompt(56, n=38), max_new_tokens=4)
        ticks, spent = 0, []
        n0 = len(split.slots[0].generated)
        while ticks == 0 or 1 in split._prefilling:
            split.step()
            ticks += 1
            spent.append(split._tick_work["prompt_tokens"])
            assert split._tick_work["decode_slots"] >= 1
        assert max(spent) == per_tick <= max(budget, 4)
        assert sum(spent) == -(-38 // width) * width
        assert split.slots[0].rid == fast
        assert len(split.slots[0].generated) == n0 + ticks
        split.drain()

    def test_older_admission_gets_the_ticks_tokens(self, model):
        """Two slots mid-prefill under a budget: the one admitted first
        gets every token of the tick until it is done; the other's chunks
        are counted as deferred."""
        split = make_engine(
            model, max_batch=2,
            scheduler=SchedulerConfig(prefill_token_budget=4))
        old = split.add_request(prompt(57, n=12), max_new_tokens=2)
        new = split.add_request(prompt(58, n=12), max_new_tokens=2)
        split.step()
        assert list(split._prefilling) == [0, 1]
        assert [split.slots[i].rid for i in (0, 1)] == [old, new]
        split.step()
        assert split._prefilling[0]["next"] == 2
        assert split._prefilling[1]["next"] == 0
        # left after tick 1: 2 + 3 chunks; after tick 2: 1 + 3
        assert split.scheduler.deferred_chunks == 9
        out = split.step()                  # the older one's last chunk
        assert list(split._prefilling) == [1]
        out.update(split.run_to_completion())
        assert out[old] == ref_greedy(model, prompt(57, n=12), 2)
        assert out[new] == ref_greedy(model, prompt(58, n=12), 2)

    def test_token_accounting(self, model):
        eng = make_engine(
            model, scheduler=SchedulerConfig(prefill_token_budget=8))
        eng.add_request(prompt(54, n=10), max_new_tokens=4)
        eng.run_to_completion()
        assert eng.scheduler.prefill_tokens > 0
        assert eng.scheduler.decode_tokens > 0


# ----------------------------------------------------------- streaming
class TestStreaming:
    def test_engine_stream_yields_all_tokens(self, model):
        p = prompt(60, n=7)
        eng = make_engine(model)
        rid = eng.add_request(p, max_new_tokens=8)
        s = eng.stream(rid)
        assert isinstance(s, TokenStream)
        toks = list(s)
        assert toks == ref_greedy(model, p, 8)
        assert s.status == RequestStatus.FINISHED

    def test_stream_attached_late_replays_history(self, model):
        p = prompt(61, n=6)
        eng = make_engine(model)
        rid = eng.add_request(p, max_new_tokens=8)
        eng.step()
        eng.step()                          # some tokens already out
        toks = list(eng.stream(rid))
        assert toks == ref_greedy(model, p, 8)

    def test_stream_of_shed_request_terminates_empty(self, model):
        eng = make_engine(
            model, max_batch=1,
            res=ResilienceConfig(max_queue=8, queue_high_water=1))
        rids = [eng.add_request(prompt(62 + i), max_new_tokens=4)
                for i in range(4)]
        s = eng.stream(rids[-1])            # newest: first to shed
        eng.step()
        toks = list(s)
        assert toks == []
        assert s.status == RequestStatus.SHED
        eng.drain()

    def test_router_stream_matches_greedy(self, model):
        p = prompt(65, n=9)
        router = Router([make_engine(model) for _ in range(2)]).warmup()
        rid = router.add_request(p, max_new_tokens=8)
        toks = list(router.stream(rid))
        assert toks == ref_greedy(model, p, 8)


# --------------------------------------- sampling determinism (bugfix)
class TestSamplingDeterminismUnderPreemption:
    def _sampled_run(self, model, preempt: bool):
        """Two sampled requests; with ``preempt`` the pool is tight
        enough that one is evicted mid-flight and re-prefilled."""
        kw = (dict(num_blocks=5, max_blocks_per_seq=4) if preempt
              else dict(num_blocks=64, max_blocks_per_seq=16))
        eng = make_engine(model, seed=123, **kw)
        evictions = []
        orig = eng._evict
        eng._evict = lambda slot: (evictions.append(slot),
                                   orig(slot))[-1]
        p1, p2 = prompt(70, n=4), prompt(71, n=4)
        r1 = eng.add_request(p1, max_new_tokens=6, temperature=1.0,
                             top_p=0.9)
        r2 = eng.add_request(p2, max_new_tokens=6, temperature=1.0,
                             top_p=0.9)
        out = eng.run_to_completion(max_ticks=300)
        return out[r1], out[r2], len(evictions)

    def test_preempted_sampled_request_resumes_same_tokens(self, model):
        """The regression (ISSUE 13 bugfix): re-admission re-prefills
        the generated prefix but used to REPLAY the engine-global RNG
        stream from a shifted position, so a preempted sampled request
        diverged from its unpreempted self. Keys are per (request,
        position) now — preemption is invisible in the tokens."""
        base1, base2, ev0 = self._sampled_run(model, preempt=False)
        got1, got2, ev = self._sampled_run(model, preempt=True)
        assert ev0 == 0 and ev >= 1         # the tight run really evicted
        assert got1 == base1
        assert got2 == base2

    def test_fixed_seed_reproducible_across_engines(self, model):
        p = prompt(72, n=5)

        def run(seed):
            eng = make_engine(model, seed=seed)
            rid = eng.add_request(p, max_new_tokens=6, temperature=0.8,
                                  top_p=0.95)
            return eng.run_to_completion()[rid]

        assert run(5) == run(5)
        assert run(5) != run(6)


# ------------------------------------------------------- loadgen rider
class TestLoadgenRouterMode:
    def test_run_load_through_router_accounts_everything(self, model):
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.loadgen import run_load

        router = Router(
            [make_engine(model, max_batch=2,
                         res=ResilienceConfig(max_queue=4))
             for _ in range(2)]).warmup()
        report = run_load(router, offered_rps=500.0, n_requests=12,
                          vocab_size=97, prompt_len_range=(4, 10),
                          max_new_tokens=4, seed=3)
        router.drain()
        assert report["submitted"] == 12
        assert report["overloaded"] == 0     # router never raises
        assert report["finished"] + report["shed"] == 12
        assert report["router"] is not None
        routed = sum(r["routed"]
                     for r in report["router"]["per_replica"])
        assert routed + report["router"]["shed_at_router"] >= 12
        assert_no_leaks(router.replicas)
