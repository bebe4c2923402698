"""What the tests that serve a tiny model through ``PagedEngine`` share (a
plain module, not collected): the models, the engines over them, the recorder
of the logits a program sampled from, and the scenarios every served model is
put through.

**Models are one a kind a file, engines one a test.** Engines over ONE model
share their compiled chunk, step and mixed programs (``serving.
_PAGED_JIT_CACHE``, the path ``Router`` replicas take), so a test that changes
no weight asks ``engine(case)`` and pays a trace only for a shape no earlier
test of its file met. A test that changes a weight, or a switch a trace reads
(``paged_attention.INTERPRET``, ``_PREFILL_WIDTH``, ...), builds a model of its
own with ``build(case)``: a cached program is never run under a patch it was
not traced with. The recording sampler is such a patch, and ``model_of`` keeps
the models traced under it apart from the plain ones.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models as zoo  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedEngine, serving  # noqa: E402
from paddle_tpu.inference.resilience import RequestStatus  # noqa: E402
from paddle_tpu.serving import Router, SchedulerConfig  # noqa: E402


# ------------------------------------------------------------ small numerics
def f32_weights(weights_lib, cfg, seed, layers=None):
    """The table's bf16 draws upcast to float32: what the reference reads."""
    made = weights_lib.make(cfg, seed, jnp.bfloat16, layers=layers)
    return {k: v.astype(jnp.float32) for k, v in made.items()}


def rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def close(got, want, atol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol,
                               rtol=0)


def _is_tensor(v):
    return isinstance(v, Tensor)


def traced(fn, *args, **kw):
    """``fn(*args, **kw)`` as ONE traced program in place of an eager
    dispatch (and a compile) an op: the same function on the same values,
    and what a served or a trained model runs. Tensors and arrays among the
    arguments are the program's arguments, anything else is closed over;
    the result comes back as ``fn`` gave it, Tensors as Tensors. (A program
    a call: nothing is kept for a second call of the same shapes.)"""
    leaves, tree = jax.tree_util.tree_flatten((args, kw), is_leaf=_is_tensor)
    moving = [i for i, v in enumerate(leaves)
              if isinstance(v, (Tensor, jax.Array, np.ndarray))]
    made = {}

    def program(arrays):
        full = list(leaves)
        for i, a in zip(moving, arrays):
            full[i] = Tensor(a) if _is_tensor(leaves[i]) else a
        a, k = jax.tree_util.tree_unflatten(tree, full)
        out, made["tree"] = jax.tree_util.tree_flatten(fn(*a, **k),
                                                       is_leaf=_is_tensor)
        made["tensors"] = [_is_tensor(v) for v in out]
        return [v._data if _is_tensor(v) else v for v in out]

    out = jax.jit(program)([leaves[i]._data if _is_tensor(leaves[i])
                            else leaves[i] for i in moving])
    return jax.tree_util.tree_unflatten(made["tree"], [
        Tensor(v) if t else v for v, t in zip(out, made["tensors"])])


# ------------------------------------------------------------------- models
#: kind -> (driver, weight maker, tiny preset (module, name), model class)
#: of the benchmark's served configurations
_PRESETS = {
    "hybrid": ("serve_hybrid", "weights_nemotron_h",     # Mamba-2 state a
               ("tiny_hybrid", "NEMOTRON"),              # slot + paged K/V
               "NemotronHForCausalLM"),
    "window": ("serve_exaone_moe", "weights_exaone_moe",  # window rows a
               ("tiny_exaone_moe", "EXAONE"),             # slot + paged K/V
               "ExaoneMoeForCausalLM"),
    "latent": ("serve_deepseek_v3", "weights_deepseek_v3",   # latent pages,
               ("tiny_deepseek_v3", "DEEPSEEK"),             # a pool a layer
               "DeepseekV3ForCausalLM"),
    "linear": ("serve_olmo_hybrid", "weights_olmo_hybrid",   # a delta-rule
               ("tiny_olmo_hybrid", "CFG"),                  # matrix a head
               "OlmoHybridForCausalLM"),
    "kda": ("serve_solar_open2", "weights_solar_open2",     # a delta-rule
            ("tiny_solar_open2", "CFG"),                    # matrix a head
            "SolarOpen2ForCausalLM"),                       # + experts
}


@dataclasses.dataclass(frozen=True, eq=False)
class Case:
    """One served model: its kind, the configuration and seed its weights
    are made from (None: the benchmark's tiny preset), the scheduler budget
    its engines prefill under, and, where a file holds it against a plain
    reference, ``reference(ids) -> [T, vocab]`` logits of one sequence and
    the tolerance they are met with."""
    kind: str
    cfg: Optional[dict] = None
    seed: int = 5
    budget: Optional[int] = 16
    reference: Optional[Callable] = None
    atol: float = 0.0

    def parts(self):
        driver, weights, (preset, name), cls = _PRESETS[self.kind]
        cfg = self.cfg if self.cfg is not None else getattr(
            importlib.import_module(f"benchmark.tests.{preset}"), name)
        return (importlib.import_module(f"benchmark.drivers.{driver}"),
                importlib.import_module(f"benchmark.lib.{weights}"), cfg,
                getattr(zoo, cls))

    @property
    def vocab(self):
        if self.kind in _PRESETS:
            return self.parts()[2]["vocab_size"]
        return {"dense": 97, "gpt": 89}[self.kind]


def _case(case):
    return case if isinstance(case, Case) else Case(case)


def build(case, dtype=jnp.float32):
    """A model of its own, in ``eval()``: seeded float32 weights (the
    matrices in ``dtype``; norm scales and router biases stay float32)."""
    case = _case(case)
    if case.kind == "dense":
        paddle.seed(7)
        m = zoo.LlamaForCausalLM(zoo.LlamaConfig(
            vocab_size=97, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, max_seq_len=128,
            use_flash_attention=False))
    elif case.kind == "gpt":    # learned positions, fused qkv, tied head
        paddle.seed(11)
        m = zoo.GPTForCausalLM(zoo.GPTConfig(
            vocab_size=89, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=128, use_flash_attention=False))
    else:
        driver, weights_lib, cfg, cls = case.parts()
        m = cls(driver.model_config(cfg))
        driver.put_weights(m, {
            k: v.astype(dtype) if v.ndim > 1 else v
            for k, v in f32_weights(weights_lib, cfg, case.seed).items()})
    m.eval()
    return m


_MODELS = {}


@pytest.fixture(scope="module", autouse=True)
def models():
    """A file's models (and with them their compiled programs) go when the
    file is done: import this fixture into every file that uses
    ``model_of``."""
    _MODELS.clear()
    yield
    _MODELS.clear()


def model_of(case):
    """The file's one model of this kind, configuration and seed (no test
    changes a weight of it), kept apart by whether its programs are traced
    under the recording sampler."""
    case = _case(case)
    key = (case.kind, json.dumps(case.cfg, sort_keys=True, default=str),
           case.seed, serving._sample_tokens is _recording_sampler)
    if key not in _MODELS:
        _MODELS[key] = build(case)
    return _MODELS[key]


# ------------------------------------------------------------------ engines
def engine(case="dense", *, model=None, serial=False, usable=None, **kw):
    """A new engine over the file's model of ``case`` (or over ``model``, a
    test's own). ``budget=`` is the scheduler's prefill budget a tick (the
    case's unless given), ``serial`` reads every program before the next is
    launched, ``usable`` holds blocks back through the block manager until
    that many are left: a scarce pool with the arrays, and so the programs,
    of the common one."""
    case = _case(case)
    budget = kw.pop("budget", case.budget)
    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_blocks_per_seq", 16)
    if budget:
        kw.setdefault("scheduler",
                      SchedulerConfig(prefill_token_budget=budget))
    eng = PagedEngine(model or model_of(case), **kw)
    if serial:
        eng._overlap = False
    if usable is not None:
        eng.bm.allocate(eng.bm.available - usable)      # never released
        eng._total_usable = usable
    return eng


def prompts_of(case, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, _case(case).vocab, n).tolist() for n in lengths]


LENGTHS = (5, 17, 33, 8, 40, 3, 21)
#: answers of one token (never fed to a decode step) to a dozen
NEW = (6, 1, 9, 2, 12, 7, 4)


def serve(eng, prompts, new=NEW, sampled=False, streams=True):
    """``{index: tokens}`` of every request, its stream checked against
    its outcome on the way."""
    rids, bufs = [], []
    for i, (p, n) in enumerate(zip(prompts, new)):
        warm = sampled and i % 3 != 2        # a greedy lane among sampled
        rids.append(eng.add_request(
            p, max_new_tokens=n, temperature=0.8 if warm else 0.0,
            top_p=0.9 if warm else 1.0))
        bufs.append(eng.open_stream(rids[-1]) if streams else None)
    out = eng.run_to_completion(max_ticks=2000)
    assert eng.tick_failures == 0
    served = {}
    for i, rid in enumerate(rids):
        oc = eng.outcomes[rid]
        assert oc.status == RequestStatus.FINISHED, (i, oc.status, oc.detail)
        assert out[rid] == oc.tokens and len(oc.tokens) == new[i]
        if streams:
            assert bufs[i] == oc.tokens
        served[i] = oc.tokens
    quiesced(eng)
    return served


def quiesced(eng):
    assert eng._unread is None and not eng.has_work()
    assert not eng.queue and all(s is None for s in eng.slots)
    assert eng.bm.available == eng._total_usable, "leaked KV blocks"
    assert not eng._inflight.any()


def watch_evictions(eng):
    """The slots the engine preempts from here on, in order."""
    evicted = []
    evict = eng._evict
    eng._evict = lambda slot: (evicted.append(slot), evict(slot))[-1]
    return evicted


# ------------------------------------------------- the model's own greedy
def greedy_over(model, prompt, served):
    """The token the model's own forward puts first after ``prompt +
    served[:n]``, for every n: ``served`` itself where it is the model's
    greedy continuation, token for token what a decode that recomputes the
    whole sequence at every length gives (by induction over n: a causal
    model's logits at a position see nothing after it). ONE forward, with
    the row padded at the end to the model's longest sequence: one shape a
    model, no trace at every length."""
    ids = list(prompt) + list(served[:-1])
    padded = np.zeros((1, model.cfg.max_seq_len), np.int64)
    padded[0, :len(ids)] = ids
    logits = np.asarray(model(paddle.to_tensor(padded)).numpy())[0]
    return [int(t) for t in
            logits[len(prompt) - 1:len(ids)].argmax(-1)]


def assert_greedy(model, prompt, served, n_new):
    """``served`` is the ``n_new`` tokens of the model's own greedy decode."""
    assert len(served) == n_new
    assert list(served) == greedy_over(model, prompt, served)


# ---------------------------------------------------------------- recording
_OPEN = None            # the running test's recorder, if it opened one


def _note(logits, rids, ngens):
    if _OPEN is not None:
        for row, rid, n in zip(np.asarray(logits), np.asarray(rids),
                               np.asarray(ngens)):
            if rid:
                _OPEN.rows[(int(rid), int(n))] = row


_plain_sampler = serving._sample_tokens


def _recording_sampler(logits, temps, top_ps, base_key, rids, ngens,
                       sampling):
    jax.debug.callback(_note, logits, rids, ngens, ordered=True)
    return _plain_sampler(logits, temps, top_ps, base_key, rids, ngens,
                          sampling)


@pytest.fixture(scope="module", autouse=True)
def recording():
    """``serving._sample_tokens`` wrapped with a host callback for the whole
    file that imports this fixture: every program the file traces hands the
    logits it samples from to the recorder the running test opened (to
    nobody where it opened none)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(serving, "_sample_tokens", _recording_sampler)
    yield
    jax.effects_barrier()
    patch.undo()


class Recorder:
    """The logits every program call samples from, keyed by (request,
    tokens generated so far). A lane that ran under the ``seq = 0``
    sentinel writes garbage under its key and the real step overwrites it
    later. ``rows`` starts empty: what an earlier test's programs still
    had to say is waited for first."""

    def __init__(self):
        assert serving._sample_tokens is _recording_sampler, \
            "import served.recording into the file"
        self.clear()

    def clear(self):
        jax.effects_barrier()
        self.rows = {}


@pytest.fixture
def rec(recording):
    global _OPEN
    _OPEN = Recorder()
    yield _OPEN
    _OPEN = None


def check_against_reference(rec, rid, prompt, served, reference, atol):
    """Every row request ``rid``'s tokens were sampled from against
    ``reference(ids)`` over prompt + served tokens. The row is padded at the
    end to a multiple of 32 tokens (a causal model's earlier positions never
    see what follows them), so the reference compiles for a few lengths, not
    for every request's own."""
    ids = list(prompt) + list(served[:-1])
    padded = np.zeros(-(-len(ids) // 32) * 32, np.int32)
    padded[:len(ids)] = ids
    want = np.asarray(reference(padded))
    for n in range(len(served)):
        close(rec.rows[(rid, n)], want[len(prompt) - 1 + n], atol)


def check_served(case, rec, served, prompts, rids=None, atol=None):
    """``served[rid]`` of every request against the case's reference."""
    jax.effects_barrier()
    for rid, p in zip(rids or sorted(served), prompts):
        check_against_reference(rec, rid, p, served[rid], case.reference,
                                case.atol if atol is None else atol)


# ---------------------------------------------------------------- scenarios
def served_logits_are_the_references(case, rec, front, *, width, new,
                                     probe=None):
    """Prefill in chunks of ``width`` (left-padded first chunk), then decode
    through the cache, four requests of unequal length sharing the batch:
    every logits row the programs sampled from against the reference's full
    forward over prompt + served tokens. By ``front``: the engine itself,
    the engine behind a warmed-up ``Router``, or the ``serial`` schedule
    (no overlap, no mixed step). Returns the engine and, where ``probe`` is
    given, ``probe(eng)`` as it read just before the requests went in."""
    eng = engine(case, serial=front == "serial")
    assert eng.prefill_width == width
    prompts = prompts_of(case, (5, 40, 70, 32))
    if front == "router":
        door = Router([eng]).warmup()     # placement needs a READY replica
        rec.clear()                       # the warm-up request's rows
        before = probe(eng) if probe else None
        rids = [door.add_request(p, max_new_tokens=new) for p in prompts]
        while door.has_work():
            door.step()
        served = {r: door.outcomes[r].tokens for r in rids}
        assert all(door.outcomes[r].status == "FINISHED" for r in rids)
    else:
        before = probe(eng) if probe else None
        rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
        served = eng.run_to_completion()
    jax.effects_barrier()
    engine_rids = sorted({rid for rid, _n in rec.rows})
    assert len(engine_rids) == 4
    for erid, rid, p in zip(engine_rids, rids, prompts):
        check_against_reference(rec, erid, p, served[rid], case.reference,
                                case.atol)
    return eng, prompts, before


def a_reused_slot_starts_clean(case, rec, lengths):
    """One slot, two requests one after the other: the second's logits are
    the reference's, which starts from nothing."""
    eng = engine(case, max_batch=1)
    first, second = prompts_of(case, lengths, seed=1)
    a = eng.add_request(first, max_new_tokens=5)
    out_a = eng.run_to_completion()[a]
    b = eng.add_request(second, max_new_tokens=5)
    out_b = eng.run_to_completion()[b]
    check_served(case, rec, {a: out_a, b: out_b}, (first, second))


def a_lane_mid_prefill_keeps_what_it_holds(case, rec):
    """A budget of 8 prompt tokens a tick: the 45-token prompt is mid-way
    for six ticks while the short request decodes in every one of them (its
    lane rides those decode steps under the seq = 0 sentinel)."""
    eng = engine(case, budget=8)
    short, long_ = prompts_of(case, (6, 45), seed=2)
    a = eng.add_request(short, max_new_tokens=12)
    b = eng.add_request(long_, max_new_tokens=4)
    overlapped, served = 0, {}
    while eng.has_work():
        mid = len(eng._prefilling)
        decoding = len(eng._decode_lanes())
        served.update(eng.step())
        overlapped += bool(mid and decoding)
    assert overlapped >= 3
    check_served(case, rec, served, (short, long_), (a, b))


def a_memory_stalled_lane_keeps_what_it_holds(case, rec):
    """Five usable blocks, two requests that need three each: the second
    waits out the first's last steps under the seq = 0 sentinel, then goes
    on from what it held."""
    eng = engine(case, usable=5)
    stalls = []
    plan = eng._plan_decode

    def watching(active, *aboard):
        got = plan(active, *aboard)
        if got is not None:
            stalls.append(list(got[-1]))
        return got

    eng._plan_decode = watching
    p, q = prompts_of(case, (7, 7), seed=3)
    a = eng.add_request(p, max_new_tokens=12)
    b = eng.add_request(q, max_new_tokens=16)
    served = eng.run_to_completion(max_ticks=200)
    assert any(s for s in stalls)
    check_served(case, rec, served, (p, q), (a, b))


def evict_then_readmit_reproduces_the_logits(case, rec, *, usable, length,
                                             new, max_ticks):
    """Every lane stalled: one is preempted, its blocks freed, and it is
    re-prefilled over prompt + generated tokens later. What a slot holds
    beside its pages needs no free and no snapshot: the re-prefill makes it
    again. Returns the prompts and their tokens."""
    eng = engine(case, usable=usable)
    evicted = watch_evictions(eng)
    p, q = prompts_of(case, (length, length), seed=4)
    a = eng.add_request(p, max_new_tokens=new)
    b = eng.add_request(q, max_new_tokens=new)
    served = eng.run_to_completion(max_ticks=max_ticks)
    assert evicted
    check_served(case, rec, served, (p, q), (a, b))
    return (p, q), [served[a], served[b]]


def whole_sequence_forward_is_differentiable(case, ids):
    """Eager autograd from ``forward(ids)`` of the file's model in ``train()``
    reaches every parameter with a finite gradient that is not all zero (a
    router's correction bias only steers a choice: its gradient is zero).
    The model is handed back as it was: in ``eval()``, no gradient kept."""
    m = model_of(case)
    m.train()
    try:
        out = m(paddle.to_tensor(ids))
        (out * out).mean().backward()
        for name, p in m.named_parameters():
            assert p.grad is not None, name
            g = p.grad.numpy()
            assert np.isfinite(g).all(), name
            if not name.endswith("e_score_correction_bias"):
                assert np.abs(g).max() > 0, name
    finally:
        m.clear_gradients()
        m.eval()
