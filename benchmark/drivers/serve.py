"""Serving cells: the configuration's decoder through
``serving.Router`` -> one ``inference.PagedEngine`` replica, driven by the
benchmark's own load generator (``benchmark/lib/traffic.py``).

The generator is one thread: it submits what is due, ticks the router, and
stamps every token with its OWN clock when the tick that produced it returns
(tokens are read from the request's stream buffer, never from the program's
time records). Open loop: requests are sent on the schedule whatever the
engine does, and time to first token runs from when a request was DUE.
Closed loop: each client sends its next request when its last finishes.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark.lib import check as check_lib
from benchmark.lib import harness, stats, traffic as traffic_lib, weights
from benchmark.lib.harness import log

_LLAMA_GLOBAL = {"model.embed_tokens.weight": "embed",
                 "model.norm.weight": "norm", "lm_head.weight": "lm_head"}
_LLAMA_BLOCK = {"input_layernorm.weight": "input_norm",
                "self_attn.q_proj.weight": "q", "self_attn.k_proj.weight": "k",
                "self_attn.v_proj.weight": "v", "self_attn.o_proj.weight": "o",
                "post_attention_layernorm.weight": "post_norm",
                "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
                "mlp.down_proj.weight": "down"}
FAILURES = ("FAILED", "SHED", "DEADLINE_MISSED")


def llama_key(param_name: str):
    if param_name in _LLAMA_GLOBAL:
        return (-1, _LLAMA_GLOBAL[param_name])
    _model, _layers, layer, leaf = param_name.split(".", 3)
    return (int(layer), _LLAMA_BLOCK[leaf])


def build(cfg: dict, seed: int):
    """``(router, replica, model)``: the seed's bf16 weights made on the
    device in one call, one warmed PagedEngine behind a Router."""
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import Router, SchedulerConfig

    if cfg["arch"] != "llama_like":
        raise SystemExit(f"serve driver has no model for arch {cfg['arch']!r}")
    eng = cfg["engine"]
    hd = cfg.get("head_dim") or (cfg["hidden_size"]
                                 // cfg["num_attention_heads"])
    if hd * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit("LlamaConfig derives head_dim as hidden / heads")
    with LazyGuard():
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            max_seq_len=eng["context"], rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], use_flash_attention=False,
            tie_embeddings=bool(cfg.get("tie_word_embeddings"))))
    log("model described")
    load_weights(model, cfg, seed)
    materialize_layer(model)
    import jax
    jax.block_until_ready([p._data for p in model.parameters()])
    log("weights made")
    budget = eng.get("prefill_token_budget")
    replica = PagedEngine(
        model, max_batch=eng["max_batch"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["context"] // eng["block_size"],
        scheduler=(SchedulerConfig(prefill_token_budget=budget)
                   if budget else None))
    log("engine built")
    router = Router([replica]).warmup()
    log("engine warm")
    return router, replica, model


def load_weights(model, cfg, seed):
    """Put the seed's weights into the model's parameters (a model still
    lazy gets them as its initialiser; a live one has them swapped in)."""
    made = weights.make("llama_like", cfg, seed, "bfloat16")
    for name, p in model.named_parameters():
        arr = made.pop(llama_key(name))
        if getattr(p, "_lazy_init", None) is not None:
            p._lazy_init = (lambda _s, _d, a=arr: a, tuple(arr.shape),
                            arr.dtype)
        else:
            p._swap_payload(arr)
    if made:
        raise RuntimeError(f"weights without a parameter: {sorted(made)}")


class Live:
    """The generator's record of one request."""

    __slots__ = ("req", "rid", "due", "sent", "buf", "seen", "stamps",
                 "slot_t", "status")

    def __init__(self, req, rid, due, sent, buf):
        self.req, self.rid, self.due, self.sent, self.buf = (
            req, rid, due, sent, buf)
        self.seen, self.stamps, self.slot_t, self.status = 0, [], None, None


def drive(router, replica, plan: dict, seconds: float, grace_s: float = 0.0,
          observe: bool = False, tracer=None, trace_from=None,
          alter_token=None) -> dict:
    """The measured window. Returns the generator's records: per request
    its due / sent times and token stamps, per tick its span and what ran.
    ``observe`` adds the per-tick reads only the per-layer metrics need
    (queue depth, slots, who holds a slot). ``alter_token`` is the tests'
    hook: it may change a served token where the generator reads it."""
    import jax

    clock = time.perf_counter
    reqs = plan["requests"]
    open_loop = plan["loop"] == "open"
    free_clients = 0 if open_loop else plan["clients"]
    nxt, live, records, ticks = 0, {}, [], []
    t0 = clock()
    tracing, trace_tick0 = False, None

    def submit(req, now):
        rid = router.add_request(req["prompt"],
                                 max_new_tokens=req["max_new_tokens"])
        rec = Live(req, rid, req["due"] if open_loop else now, now,
                   router.stream(rid)._buf)
        live[rid] = rec
        records.append(rec)

    while True:
        now = clock() - t0
        if now >= seconds:
            waiting = [r for r in live.values() if not r.stamps]
            if not (open_loop and waiting and now < seconds + grace_s):
                break
        if tracer is not None and not tracing and now >= trace_from:
            tracer.start()
            tracing, trace_tick0 = True, len(ticks)
        if now < seconds:
            if open_loop:
                while nxt < len(reqs) and reqs[nxt]["due"] <= now:
                    submit(reqs[nxt], now)
                    nxt += 1
            else:
                while free_clients and nxt < len(reqs):
                    submit(reqs[nxt], now)
                    nxt += 1
                    free_clients -= 1
        if not router.has_work():
            if open_loop and nxt < len(reqs):
                time.sleep(min(max(reqs[nxt]["due"] - now, 0.0), 0.002))
            else:
                time.sleep(0.001)
            continue
        queued = len(replica.queue) if observe else 0
        active = replica.num_active if observe else 0
        t_a = clock()
        if tracing:
            with jax.profiler.TraceAnnotation("bench.step"):
                router.step()
        else:
            router.step()
        t_b = clock()
        firsts = decodes = 0
        cached = 0
        for rid in list(live):
            rec = live[rid]
            n = len(rec.buf)
            if n > rec.seen:
                if alter_token is not None:
                    for j in range(rec.seen, n):
                        rec.buf[j] = alter_token(rec, j, rec.buf[j])
                if rec.seen == 0:
                    firsts += 1
                    decodes += n - 1
                else:
                    decodes += n - rec.seen
                    cached += len(rec.req["prompt"]) + rec.seen
                rec.stamps += [t_b - t0] * (n - rec.seen)
                rec.seen = n
            if observe and rec.slot_t is None and (
                    rec.seen or router.request_status(rid) == "RUNNING"):
                rec.slot_t = t_a - t0
            if rec.seen >= rec.req["max_new_tokens"] or (
                    rid in router.outcomes):
                del live[rid]
                if not open_loop:
                    free_clients += 1
        prefilling = replica.health()["prefilling"] if observe else 0
        ticks.append((t_a - t0, t_b - t0, firsts, decodes, queued, active,
                      cached, prefilling))
    t_end = clock() - t0
    if tracing:
        tracer.stop()
    # the cut: the window closes on requests still in flight (finishing
    # them would cost up to the longest answer again); each is then either
    # in a terminal status or known to the router as queued or running
    outcomes = dict(router.outcomes)
    for rec in records:
        oc = outcomes.get(rec.rid)
        rec.status = (oc.status if oc is not None
                      else router.request_status(rec.rid))
        if oc is not None and rec.status == "FINISHED":
            rec.req["served"] = list(rec.buf)
            rec.req["served_by_program"] = list(oc.tokens)
    return {"records": records, "ticks": ticks, "window_s": t_end,
            "offered": nxt, "seconds": seconds, "trace_tick0": trace_tick0}


def ran_prefill(tick) -> bool:
    """A tick ran a prefill chunk if a request got its first token in it
    or a prompt was still part-way through when it returned (the second is
    only read in an observed run)."""
    return bool(tick[2] or tick[7])


def reduce_window(win: dict, open_loop: bool) -> dict:
    """Latencies and rates from the generator's records."""
    records, seconds = win["records"], win["seconds"]
    failed = sum(1 for r in records if r.status in FAILURES
                 or r.status is None)
    ttft = [(r.stamps[0] - r.due) if r.stamps else math.inf
            for r in records if r.status not in FAILURES]
    ttft = stats.latencies_with_failures(ttft, failed)
    gaps = [b - a for r in records for a, b in zip(r.stamps, r.stamps[1:])]
    tokens_in_window = sum(1 for r in records for s in r.stamps
                           if s <= seconds)
    late = [r.sent - r.due for r in records] if open_loop else []
    half = [[1e3 * (r.stamps[0] - r.due) for r in records
             if r.stamps and (r.due < seconds / 2) == first]
            for first in (True, False)]
    out = {"backlog_at_close": sum(1 for r in records if not r.stamps
                                   or r.stamps[0] > seconds),
           "ttft_p50_ms_halves": [stats.median(h) if h else None
                                  for h in half],
           "attempted": len(records), "failed": failed,
           "finished": sum(1 for r in records if r.status == "FINISHED"),
           "serve_tokens_per_s": tokens_in_window / seconds,
           "itl_p95_ms": 1e3 * stats.percentile(gaps, 95) if gaps else None,
           "n_gaps": len(gaps), "tokens": tokens_in_window}
    if open_loop:
        out["ttft_p90_ms"] = 1e3 * stats.percentile(ttft, 90)
        out["gen_late_p95_ms"] = 1e3 * stats.percentile(late, 95)
    return out


def dump_records(workload: str, seed: int, win: dict):
    """What the generator recorded, for whoever wants another statistic
    than the line's: ``.bench_out/records/<workload>.json`` in the checkout
    (a fixed path, overwritten by the next run of the cell)."""
    import json
    import os
    path = os.path.join(harness.ROOT, ".bench_out", "records",
                        workload + ".json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"seed": seed, "seconds": win["seconds"],
                   "window_s": win["window_s"],
                   "requests": [{"due": r.due, "sent": r.sent,
                                 "prompt": len(r.req["prompt"]),
                                 "max_new": r.req["max_new_tokens"],
                                 "status": r.status, "slot_t": r.slot_t,
                                 "stamps": r.stamps} for r in win["records"]],
                   "ticks": win["ticks"]}, f)


def pick_sample(records, n: int, seed: int):
    """A sample of the finished requests, drawn from the seed, with the
    longest (prompt + served tokens) in it."""
    done = [r for r in records if r.status == "FINISHED"
            and r.req.get("served")]
    if not done:
        return []
    done.sort(key=lambda r: r.req["index"])
    longest = max(done, key=lambda r: len(r.req["prompt"])
                  + len(r.req["served"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    picks = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in picks]


def compare_with_reference(cfg, seed, sample, verdict, control=False):
    from benchmark.reference import llama_like as ref
    t0 = time.perf_counter()
    got = ref.served_token_gaps(
        cfg, seed, [r.req["prompt"] for r in sample],
        [r.req["served"] for r in sample], cfg["engine"]["context"],
        control=control)
    log(f"{'control' if control else 'reference'} over {len(sample)} "
        f"requests, {got['positions']} served tokens: "
        f"{time.perf_counter() - t0:.1f}s, top1 share "
        f"{got['top1_share']:.4f}, mean gap {got['logit_gap_mean']:.5f}, "
        f"widest {got['logit_gap_max']:.5f}")
    if verdict is not None:
        for name in ("logit_gap_mean", "logit_gap_max"):
            verdict.compare(name, got[name], cfg["check"][name])
    return got


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        t_process: float, alter_token=None) -> dict:
    cfg, mix = cell["config"], cell["traffic"]
    open_loop = mix["loop"] == "open"
    clock = harness.CompileClock()
    verdict = check_lib.Verdict()
    if trace:
        import paddle_tpu as paddle
        paddle.set_flags({"FLAGS_enable_metrics": True})

    plan = traffic_lib.requests(mix, seed, seconds, cfg["vocab_size"])
    router, replica, model = build(cfg, seed)
    tracer = harness.TraceWindow(harness.trace_dir(cell["cell"]["name"]))
    compiles0 = clock.count
    setup_s = time.perf_counter() - t_process
    with harness.FreezeWatch() as watch:
        win = drive(router, replica, plan, seconds,
                    grace_s=mix.get("first_token_grace_s", 0.0),
                    observe=trace, tracer=tracer if trace else None,
                    trace_from=max(0.0, seconds - harness.TRACE_SECONDS),
                    alter_token=alter_token)
    slow = sorted((round(b - a, 2) for a, b, *_ in win["ticks"]),
                  reverse=True)[:3]
    log(f"process stood still {watch.freezes} s; longest ticks {slow} s")
    red = reduce_window(win, open_loop)
    dump_records(cell["cell"]["name"], seed, win)
    compiles_in_window = clock.count - compiles0
    tick_failures = replica.tick_failures
    max_batch = replica.max_batch
    device = harness.device_report(devices)

    verdict.require("no_compile_in_window", compiles_in_window == 0,
                    f"{compiles_in_window} compiles")
    verdict.require("none_lost",
                    all(r.status is not None for r in win["records"]),
                    f"{sum(r.status is None for r in win['records'])} lost")
    verdict.require("no_tick_failures", tick_failures == 0,
                    f"{tick_failures}")
    verdict.require("no_failed_requests", red["failed"] == 0,
                    f"{red['failed']} of {red['attempted']}")
    verdict.require(
        "stream_equals_outcome",
        all(r.req["served"] == r.req["served_by_program"] or alter_token
            for r in win["records"] if r.status == "FINISHED"))

    # the program's state goes before the reference's arrives, so that the
    # peak stays the program's
    sample = pick_sample(win["records"], mix["check_requests"], seed)
    del router, replica, model
    gc.collect()
    if verdict.require("finished_some", bool(sample), f"{red['finished']}"):
        compare_with_reference(cfg, seed, sample, verdict)

    metrics = {"setup_s": setup_s,
               "serve_tokens_per_s": red["serve_tokens_per_s"],
               "itl_p95_ms": red["itl_p95_ms"]}
    if open_loop:
        metrics["ttft_p90_ms"] = red["ttft_p90_ms"]
    ctx = {"kind": "serve", "config": cfg, "traffic": mix,
           "chips": len(devices), "device_kind": devices[0].device_kind,
           "window": win, "reduced": red, "max_batch": max_batch,
           "setup_compile_s": clock.total,
           "trace": tracer.reduce() if trace else None}
    log(f"window {win['window_s']:.2f}s: {red['attempted']} sent, "
        f"{red['finished']} finished, {red['failed']} failed, "
        f"{red['tokens']} tokens, {len(win['ticks'])} ticks, backlog at "
        f"close {red['backlog_at_close']}, ttft p50 by half "
        f"{red['ttft_p50_ms_halves']}; metrics "
        f"{ {k: round(v, 3) for k, v in metrics.items() if v is not None} }")
    return {"correct": verdict.correct, "attempted": red["attempted"],
            "failed": red["failed"], "metrics": metrics, "ctx": ctx,
            "numbers": verdict.numbers(), "device": device}


def control(cell: dict, seed: int, devices, seconds: float = 8.0) -> dict:
    """The control's readings: a short window at the cell's own load gives
    the prompts and served tokens; the reference in int8 is then put in the
    program's place at every served position."""
    cfg, mix = cell["config"], cell["traffic"]
    plan = traffic_lib.requests(mix, seed, seconds, cfg["vocab_size"])
    router, replica, model = build(cfg, seed)
    win = drive(router, replica, plan, seconds)
    sample = pick_sample(win["records"], mix["check_requests"], seed)
    del router, replica, model
    gc.collect()
    sound = compare_with_reference(cfg, seed, sample, None)
    low = compare_with_reference(cfg, seed, sample, None, control=True)
    return {"program": sound, "control": low}
