#!/usr/bin/env python
"""Serving benchmark: synthetic Poisson arrivals through the continuous-
batching engine (tnn_tpu/serving/), reporting TTFT and decode tokens/sec.

Unlike the offline decode benchmarks (model_bench's gpt2 rows time a fixed
batch decoding in lockstep), this measures the SERVING path: requests arrive
staggered, join and leave the running batch continuously, and contend for the
paged KV pool — so the numbers include scheduling, prefill interleave, and
page gather/scatter overheads.

    python -m benchmarks.serve_bench [--quick] [--smoke]

--smoke runs a tiny randomly initialized GPT-2 (2L/32d) — seconds on CPU,
exercising the whole engine; it is what tests/test_benchmarks.py runs.

Both modes also run a mixed-load chunked/whole A/B: long prompts arriving
under decode load, once with chunked prefill (the default) and once with the
whole-prompt path (chunked_prefill=False), reporting ttft_ms_p50/p99 and
decode_stall_ms_p50/p99/max so the step-packing win (no monolithic prefill
stalling the decode stream) is visible in regression.csv.

Both modes also run a shared-system-prompt A/B (bench_prefix): N requests
repeating one long common prefix with distinct tails, once with the prefix
cache (default) and once without, reporting prefill_tokens_saved,
prefix_hit_rate, and ttft_ms_p50/p99 — the automatic-prefix-caching win
(skip recomputing shared KV) lands in the same regression.csv.

Both modes also run a speculative-decoding A/B (bench_spec): repetitive
(cyclic) prompts decoded greedily with spec off, n-gram self-drafting, and
(smoke) the tiny draft model — reporting decode tok/s,
token_latency_ms_p50/p99, spec_acceptance_rate, and the headline
mean_accepted_per_step (> 1 means every verified mixed step committed more
than one token at token-exact greedy output).

--chaos runs the smoke workload under a seeded FaultPlan (pool-alloc
failures + injected NaN logits + corrupted speculative drafts, spec=ngram)
and asserts the fault-tolerance contract: every request terminal, zero
leaked blocks, pool invariants clean, and every surviving request
byte-identical to a fault-free spec-off run. It is a robustness gate shaped
like a benchmark row, so regressions show up in the same regression.csv
pipeline as performance.

--avail runs a replicated-availability A/B (bench_availability): the same
Poisson trace through a ``Router`` over N supervised replicas, once
untouched and once with one replica hard-killed mid-run — the
goodput_at_slo / ttft_ms_p99 delta between the twin rows is the measured
cost of losing 1 of N replicas, and the killed row self-asserts the
failover contract (exactly one terminal per request, token-exact resumed
streams, survivor pools zero-leak, clean drain). The full-model mode adds
the same A/B at 3 replicas.

Both modes also run a quantized-serving A/B (bench_quant): the same
up-front greedy batch through the f32 engine, the int8 paged-KV engine
(``kv_dtype="int8"``), and int8 KV + int8 weights (``quant_weights=True``)
— reporting decode tok/s and TTFT beside the quantization quality columns
(top-1/top-k agreement with the teacher-forced f32 argmax, teacher-forced
ppl_delta vs the f32 row) and the capacity headline max_concurrent_at_slo,
computed hbm_fit-style from the pool's ACTUAL per-token residency (int8
pages + f32 scale sidecars), not an assumed f32 itemsize. The smoke rows
persist as benchmarks/results/quant_ab_smoke.json.

--tp runs a tensor-parallel A/B (bench_tp): the same up-front greedy batch
through the paged engine at tp=1 vs tp=2 (attention heads + paged KV pool
sharded over a TP mesh, one all-reduce per layer). The tp row self-asserts
token-exact streams vs the tp=1 reference; the headline is per-chip
capacity — kv_bytes_per_token_per_shard divides exactly by tp and
max_concurrent_at_slo (requests fitting a fixed PER-CHIP HBM budget) rises
with it. Needs >=2 JAX devices; rows persist as
benchmarks/results/tp_ab_smoke.json.

--longctx runs a sequence-parallel long-context A/B (bench_longctx): the
SAME per-chip KV footprint (blocks_per_chip pool blocks per device) at
sp=1 vs sp=2 vs sp=4 over the context mesh. Every gate is deterministic:
max_context_blocks scales EXACTLY ~N x (sp * (blocks_per_chip - 1), one
scratch block per shard) while per-chip residency stays flat, the short
decode batch is token-identical to the sp=1 reference, and the
long-prompt row — a prompt whose KV exceeds ONE chip's pool — serves
token-exact against the teacher-forced greedy reference at sp>1 and is
rejected with a pointed admission error (not an OOM) at sp=1. Prefill
wall-clock for the long prompt rides the artifact's info section: on a
real mesh each shard sweeps 1/sp of the pages per layer, but the virtual
CPU mesh timeshares one core, so the deterministic stand-in — per-shard
table span exactly assembly_width/sp — is gated instead. Needs >=2 JAX
devices (the sp=4 row needs 4); rows persist as
benchmarks/results/longctx_ab_smoke.json.

--spike runs an elastic-fleet A/B (bench_spike): the same two-phase
arrival trace (gentle trickle, then a Poisson burst) through a Router of
host-tier-enabled replicas, once pinned at 1 replica (autoscaler off) and
once under a load-driven ``Autoscaler`` (scale up under the burst, graceful
zero-loss scale-down after it) — reporting goodput-at-SLO, shed/rejected
counts, a replicas-over-time timeline, and the host-RAM KV tier's hit rate
on a working set larger than the device pool (probed deterministically
against a no-tier baseline whose hit rate is zero by construction). The on
row self-asserts its goodput strictly beats the off twin's and that the
tier probe readmitted at least one block; both rows assert exactly one
terminal per request, token-exact survivors, and zero leaked blocks in
every replica's device pool and host tier. Rows persist as
benchmarks/results/spike_ab_smoke.json.

--disagg runs a disaggregated-serving A/B (bench_disagg): the same
long-prompt + short-chat mix through a 3-replica Router, all-mixed vs
prefill/decode roles with recompute-resume handoff vs roles with real
KV-block handoff + the fleet-wide prefix directory. Prefill is charged a
per-token cost (FaultPlan.prefill_delay_per_token_s) so long chunks
genuinely stall co-scheduled decodes; the kv row asserts chat TTFT p99
and decode-stall p99 strictly improve vs the mixed twin, that every long
prompt crossed the boundary with zero fault-free fallbacks, token-exact
streams, and zero leaked blocks — plus two deterministic probes: KV
handoff strictly cheaper than recompute on the receiver (counted in
prefill chunks, not wall-clock) and the fleet prefix directory strictly
beating the per-replica baseline on an identical trace. Rows persist as
benchmarks/results/disagg_ab_smoke.json.

Both modes end with a bench_load row: sustained closed-loop users plus
open-loop background arrivals driven through the supervised runtime
(``EngineSupervisor``) with one injected engine-loop crash — reporting
goodput at a TTFT SLO, shed/rejected/restart counters, and
drain_duration_s, and self-asserting the resilience contract (all
requests terminal, zero leaks, clean exit-0 drain).
"""
import argparse
import itertools
import time


import jax
import numpy as np

from benchmarks.common import RowRunner, report, write_artifact


def bench_serving(model, params, *, num_requests: int, rate_per_s: float,
                  prompt_len: int, max_new: int, num_blocks: int,
                  block_size: int, max_batch_size: int, label: str,
                  seed: int = 0, decode_path: str = "auto",
                  chunked: bool = True, chunk_size: int = 64):
    """Drive one engine through a Poisson arrival trace and report metrics."""
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    mode = f"chunk={chunk_size}" if chunked else "whole-prompt"
    print(f"{label}: {num_requests} requests, ~{rate_per_s}/s Poisson, "
          f"prompt {prompt_len}, max_new {max_new}, "
          f"decode_path={decode_path}, {mode}")
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, num_requests))
    prompts = rng.integers(0, model.vocab_size,
                           (num_requests, prompt_len)).astype(np.int32)

    engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size,
        max_seq_len=prompt_len + max_new, seed=seed,
        decode_path=decode_path, chunked_prefill=chunked,
        chunk_size=chunk_size)

    # warm the compile caches outside the timed window: one prefill at the
    # benchmark's bucket and one decode step (the engine reuses both). The
    # warmup prompt is NOT from the trace — reusing prompts[0] would publish
    # it to the prefix cache and hand the timed run a free full-cover hit
    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)
    wid = engine.submit(wprompt, 1)
    engine.run_until_complete()
    del engine.requests[wid]
    engine.metrics = ServingMetrics(engine.profiler)  # drop warmup samples

    t0 = time.perf_counter()
    next_req = 0
    while next_req < num_requests or engine.has_work:
        now = time.perf_counter() - t0
        while next_req < num_requests and arrivals[next_req] <= now:
            engine.submit(prompts[next_req], max_new)
            next_req += 1
        if engine.has_work:
            engine.step()
        elif next_req < num_requests:
            time.sleep(min(arrivals[next_req] - now, 0.05))
    wall = time.perf_counter() - t0

    s = engine.metrics.summary()
    return report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"ttft_ms_mean": s["ttft_ms_mean"],
               "ttft_ms_p50": s["ttft_ms_p50"],
               "ttft_ms_p95": s["ttft_ms_p95"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "ttft_under_load_ms_p99": s["ttft_under_load_ms_p99"],
               "decode_stall_ms_p50": s["decode_stall_ms_p50"],
               "decode_stall_ms_p99": s["decode_stall_ms_p99"],
               "decode_stall_ms_max": s["decode_stall_ms_max"],
               "token_latency_ms_p50": s["token_latency_ms_p50"],
               "prefill_chunks": s["prefill_chunks"],
               "mixed_step_fill_mean": s["mixed_step_fill_mean"],
               "preemptions": s["preemptions"],
               "batch_fill_mean": s["batch_fill_mean"],
               "requests": s["requests_finished"]})


def bench_prefix(model, params, *, num_requests: int, rate_per_s: float,
                 prefix_len: int, tail_len: int, max_new: int,
                 num_blocks: int, block_size: int, max_batch_size: int,
                 label: str, seed: int = 0, cache: bool = True,
                 chunk_size: int = 64):
    """Shared-system-prompt workload: every request repeats one long common
    prefix with a distinct tail. With the prefix cache on, requests after
    the first fork the publisher's KV blocks and chunk-prefill only their
    tails — compare prefill_tokens_saved, prefix_hit_rate, and
    ttft_ms_p50/p99 against the cache-off twin row."""
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    total = prefix_len + tail_len
    print(f"{label}: {num_requests} requests, shared prefix {prefix_len} + "
          f"tail {tail_len}, ~{rate_per_s}/s Poisson, max_new {max_new}, "
          f"prefix_cache={'on' if cache else 'off'}")
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, num_requests))
    prefix = rng.integers(0, model.vocab_size, prefix_len).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, model.vocab_size, tail_len)
                               .astype(np.int32)])
               for _ in range(num_requests)]

    engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=total + max_new,
        seed=seed, chunk_size=chunk_size, prefix_cache=cache)

    # warm the compile caches with a miniature of the real trace — a
    # DIFFERENT shared prefix plus two tails — so both the full-prompt
    # chunk bucket and (cache on) the tail-only chunk bucket are compiled
    # before the timed window; the warmup's index entries never match the
    # benchmark prefix and are evicted under pressure like any cold entry
    wrng = np.random.default_rng(seed + 1)
    wpre = wrng.integers(0, model.vocab_size, prefix_len).astype(np.int32)
    for _ in range(2):
        tail = wrng.integers(0, model.vocab_size, tail_len).astype(np.int32)
        wid = engine.submit(np.concatenate([wpre, tail]), 1)
        engine.run_until_complete()
        del engine.requests[wid]
    engine.metrics = ServingMetrics(engine.profiler)  # drop warmup samples

    t0 = time.perf_counter()
    next_req = 0
    while next_req < num_requests or engine.has_work:
        now = time.perf_counter() - t0
        while next_req < num_requests and arrivals[next_req] <= now:
            engine.submit(prompts[next_req], max_new)
            next_req += 1
        if engine.has_work:
            engine.step()
        elif next_req < num_requests:
            time.sleep(min(arrivals[next_req] - now, 0.05))
    wall = time.perf_counter() - t0

    engine.check_invariants()
    s = engine.metrics.summary()
    return report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"ttft_ms_mean": s["ttft_ms_mean"],
               "ttft_ms_p50": s["ttft_ms_p50"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "prefill_tokens_saved": s["prefill_tokens_saved"],
               "prefix_hit_rate": round(s["prefix_hit_rate"], 4),
               "prefix_lookups": s["prefix_lookups"],
               "prefix_hits": s["prefix_hits"],
               "prefix_cows": s["prefix_cows"],
               "preemptions": s["preemptions"],
               "requests": s["requests_finished"]})


def bench_spec(model, params, *, num_requests: int, prompt_len: int,
               max_new: int, num_blocks: int, block_size: int,
               max_batch_size: int, label: str, seed: int = 0,
               spec: str = "off", spec_k: int = 4, chunk_size: int = 8,
               rate_per_s: float = 50.0):
    """Speculative-decoding A/B row: a repetitive-text workload (each prompt
    cycles a short random motif) drives greedy decode with spec off, n-gram
    self-drafting, or the tiny draft model. Repetition is the representative
    case for self-drafting — code, templated text, structured output — so
    the ngram row's ``mean_accepted_per_step`` landing above 1 is the
    headline: more than one verified token per mixed step at token-exact
    greedy output (exactness itself is gated in tests/test_serving.py).
    Compare decode tok/s and token_latency_ms_p50/p99 against the off row;
    ``spec_acceptance_rate`` says how often drafted lookahead survived."""
    from tnn_tpu import models
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    print(f"{label}: {num_requests} requests, cyclic prompts {prompt_len}, "
          f"max_new {max_new}, spec={spec} k={spec_k}")
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, num_requests))
    prompts = []
    for _ in range(num_requests):
        period = int(rng.integers(2, 5))
        motif = rng.integers(0, model.vocab_size, period).astype(np.int32)
        prompts.append(np.tile(motif, prompt_len // period + 1)[:prompt_len])

    draft_model = draft_params = None
    if spec == "draft":
        draft_model = models.create("gpt2_tiny", vocab_size=model.vocab_size,
                                    max_len=model.max_len)
        draft_params = draft_model.init(
            jax.random.PRNGKey(seed + 2), (1, 8))["params"]

    engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
        seed=seed, chunk_size=chunk_size, spec=spec, spec_k=spec_k,
        draft_model=draft_model, draft_params=draft_params)

    # dedicated warmup prompt (never from the trace: see bench_serving)
    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)
    wid = engine.submit(wprompt, 2)
    engine.run_until_complete()
    del engine.requests[wid]
    engine.metrics = ServingMetrics(engine.profiler)

    t0 = time.perf_counter()
    next_req = 0
    while next_req < num_requests or engine.has_work:
        now = time.perf_counter() - t0
        while next_req < num_requests and arrivals[next_req] <= now:
            engine.submit(prompts[next_req], max_new)
            next_req += 1
        if engine.has_work:
            engine.step()
        elif next_req < num_requests:
            time.sleep(min(arrivals[next_req] - now, 0.05))
    wall = time.perf_counter() - t0

    engine.check_invariants()
    s = engine.stats()
    return report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"spec": s["spec"], "spec_k": s["spec_k"],
               "spec_draft_tokens": s["spec_draft_tokens"],
               "spec_accepted_tokens": s["spec_accepted_tokens"],
               "spec_acceptance_rate": round(s["spec_acceptance_rate"], 4),
               "mean_accepted_per_step": round(s["mean_accepted_per_step"],
                                               4),
               "token_latency_ms_p50": s["token_latency_ms_p50"],
               "token_latency_ms_p99": s["token_latency_ms_p99"],
               "ttft_ms_p50": s["ttft_ms_p50"],
               "compiled_step_signatures": s["compiled_step_signatures"],
               "requests": s["requests_finished"]})


def bench_chaos(model, params, *, num_requests: int, max_new: int,
                label: str, seed: int = 0):
    """Smoke the fault-tolerance layer: Poisson-free back-to-back submits
    under a seeded FaultPlan, asserting the terminal-state and zero-leak
    contracts. Runs with speculative decoding ON (ngram) plus corrupted
    draft proposals, so the row also gates the spec failure matrix: poisoned
    drafts and mid-spec allocation faults must cost acceptance/latency only
    — every surviving request's output is asserted byte-identical to a
    fault-free spec-off run. The row reports terminal-state counts instead
    of latency."""
    from tnn_tpu.serving import (RequestState, TERMINAL_STATES, FaultPlan,
                                 InferenceEngine)

    print(f"{label}: {num_requests} requests under seeded faults "
          f"(alloc_fail_prob=0.1, nan logits, draft poison; spec=ngram)")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, int(l)).astype(np.int32)
               for l in rng.integers(4, 14, num_requests)]
    # fault-free spec-off reference (serial: outputs are batch-independent)
    ref_engine = InferenceEngine(model, params, num_blocks=16, block_size=4,
                                 max_batch_size=4, max_seq_len=32, seed=seed)
    ref = []
    for p in prompts:
        rid = ref_engine.submit(p, max_new)
        ref.append(ref_engine.run_until_complete()[rid])
    plan = FaultPlan(seed=seed + 1, alloc_fail_prob=0.1,
                     nan_logit_calls=(4,), draft_poison_prob=0.25)
    engine = InferenceEngine(model, params, num_blocks=16, block_size=4,
                             max_batch_size=4, max_seq_len=32, seed=seed,
                             spec="ngram", spec_k=4, faults=plan)

    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new) for p in prompts]
    outs = engine.run_until_complete()
    wall = time.perf_counter() - t0

    states = [engine.result(r).state for r in rids]
    assert all(s in TERMINAL_STATES for s in states), states
    assert engine.pool.num_allocated == 0, "leaked KV blocks under chaos"
    engine.check_invariants()
    assert plan.fired["pool.alloc"] >= 1, "fault plan never fired"
    survivors_exact = all(
        outs[r] == ref[i] for i, r in enumerate(rids)
        if engine.result(r).state is RequestState.FINISHED)
    assert survivors_exact, \
        "a chaos survivor's output diverged from the fault-free run"
    s = engine.stats()
    return report(
        label, wall, items=num_requests, item_name="req",
        extra={"finished": s["requests_finished"],
               "failed": s["requests_failed"],
               "faults_fired": int(sum(plan.fired.values())),
               "draft_poison_fired": int(plan.fired["draft.poison"]),
               "survivors_exact": int(survivors_exact),
               "leaked_blocks": int(engine.pool.num_allocated),
               "step_retries": s["step_retries"],
               "terminal": int(sum(1 for st in states
                                   if st in TERMINAL_STATES))})


def bench_load(model, params, *, closed_users: int, closed_turns: int,
               open_requests: int, open_rate_per_s: float, prompt_len: int,
               max_new: int, num_blocks: int, block_size: int,
               max_batch_size: int, max_queue_depth: int, label: str,
               seed: int = 0, slo_ttft_s: float = 2.0,
               slo_stall_s: float = 1.0, crash_step: int = 0):
    """Sustained mixed load through the SUPERVISED runtime (the other rows
    drive a bare engine): ``closed_users`` closed-loop clients that resubmit
    the moment their previous request terminates, plus ``open_requests``
    open-loop Poisson arrivals at background priority 2 — so under pressure
    the bounded queue sheds/rejects the open traffic first. ``crash_step``
    injects one engine-loop crash mid-run, so the row's throughput includes
    the supervisor's recovery cost and ``engine_restarts`` proves it
    happened. Reports goodput at a TTFT SLO next to raw req/s, plus shed /
    rejected / restart counts and drain_duration_s — the operational
    counters an overloaded deployment is actually tuned by.

    The row self-asserts the resilience contract (every accepted request
    terminal, exactly one terminal event each, zero leaked blocks, clean
    drain) so a robustness regression fails the suite, not just a number.
    """
    from tnn_tpu.serving import (TERMINAL_STATES, AdmissionRejected,
                                 EngineSupervisor, FaultPlan, InferenceEngine,
                                 ServingMetrics, ShuttingDown,
                                 SupervisorState)

    total_closed = closed_users * closed_turns
    print(f"{label}: {closed_users} closed-loop users x {closed_turns} turns "
          f"+ {open_requests} open-loop @ ~{open_rate_per_s}/s (priority 2), "
          f"queue_depth {max_queue_depth}, "
          f"crash at step {crash_step or 'off'}")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / open_rate_per_s, open_requests)
    # pre-drawn prompt pool: mk_prompt is called from both the main thread
    # (open loop) and the worker thread (closed-loop resubmits), and a
    # shared Generator must not be stepped concurrently
    pool_prompts = rng.integers(
        0, model.vocab_size,
        (total_closed + open_requests + 8, prompt_len)).astype(np.int32)
    next_prompt = itertools.count()

    def mk_prompt():
        return pool_prompts[next(next_prompt) % len(pool_prompts)]

    engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
        seed=seed, max_queue_depth=max_queue_depth)

    # warm the compile caches, then reset metrics with the SLO thresholds.
    # The warmup prompt is DEDICATED, not mk_prompt(): drawing from the
    # trace pool would publish a trace prompt's KV to the prefix cache and
    # hand one timed request a free full-cover hit — inflating goodput with
    # work the warmup already paid for (and skewing the prompt counter)
    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)
    wid = engine.submit(wprompt, 1)
    engine.run_until_complete()
    del engine.requests[wid]
    engine.metrics = ServingMetrics(engine.profiler, slo_ttft_s=slo_ttft_s,
                                    slo_stall_s=slo_stall_s)
    if crash_step:
        engine.faults = FaultPlan(seed=seed + 1,
                                  step_crash_calls=(crash_step,))

    sup = EngineSupervisor(engine, max_restarts=3, restart_backoff_s=0.0,
                           drain_deadline_s=60.0)
    counters = {"terminal": 0, "not_admitted": 0}
    rids = []

    def count_terminals(ev):  # worker thread is the only mutator
        if ev["event"] != "token":
            counters["terminal"] += 1

    sup.event_sink = count_terminals

    turns = [0] * closed_users

    def start_user(uid):
        def listener(ev):
            if ev["event"] == "token":
                return
            turns[uid] += 1
            if turns[uid] < closed_turns:
                submit()

        def submit():
            # resubmits run inline on the worker thread (from the sweep)
            try:
                rids.append(sup.submit(mk_prompt(), max_new,
                                       listener=listener, priority=0))
            except (AdmissionRejected, ShuttingDown):
                counters["not_admitted"] += 1
                turns[uid] = closed_turns  # user gives up, not a hang

        submit()

    t0 = time.perf_counter()
    sup.start()
    for uid in range(closed_users):
        start_user(uid)
    for gap in gaps:  # open loop: background traffic, sheddable
        time.sleep(float(gap))
        try:
            rids.append(sup.submit(mk_prompt(), max_new, priority=2))
        except AdmissionRejected:
            pass  # counted by metrics.rejected
    deadline = time.monotonic() + 120.0
    while (counters["terminal"] < len(rids)
           or any(t < closed_turns for t in turns)):
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"load bench wedged: {counters['terminal']}/{len(rids)} "
                f"terminal, turns {turns}")
        time.sleep(0.01)
    sup.request_drain("bench complete")
    if not sup.join(timeout=60):
        raise RuntimeError("supervisor failed to drain")
    wall = time.perf_counter() - t0

    # the resilience contract IS the gate
    assert sup.state is SupervisorState.STOPPED and sup.exit_code == 0
    states = [engine.result(r).state for r in rids]
    assert all(st in TERMINAL_STATES for st in states), states
    assert counters["terminal"] == len(rids), \
        (counters["terminal"], len(rids))
    assert engine.pool.num_allocated == 0, "leaked KV blocks under load"
    engine.check_invariants()
    if crash_step:
        assert sup.restarts >= 1, "injected crash never tripped a restart"

    s = engine.metrics.summary()
    # every trace prompt is i.i.d. random and submitted once, so a prefix
    # hit in the timed window can only mean warmup KV leaked into it
    assert s["prefix_hits"] == 0, \
        "warmup leaked prefix-cache KV into the timed window"
    return report(
        label, wall, items=len(rids), item_name="req",
        extra={"finished": s["requests_finished"],
               "warmup_prefix_hits": s["prefix_hits"],
               "goodput_at_slo": round(s["goodput_at_slo"], 4),
               "slo_ttft_s": slo_ttft_s,
               "stall_slo_violations": s["stall_slo_violations"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "decode_stall_ms_p99": s["decode_stall_ms_p99"],
               "shed_requests": s["shed_requests"],
               "rejected": s["rejected"],
               "closed_not_admitted": counters["not_admitted"],
               "engine_restarts": s["engine_restarts"],
               "drain_duration_s": round(s["drain_duration_s"], 4),
               "requests_total": len(rids),
               "terminal": counters["terminal"],
               "leaked_blocks": int(engine.pool.num_allocated),
               "closed_requests": total_closed})


def bench_overlap(model, params, *, num_requests: int, prompt_len: int,
                  max_new: int, num_blocks: int, block_size: int,
                  max_batch_size: int, label: str, overlap: bool,
                  seed: int = 0, slo_ttft_s: float = 2.0,
                  slo_stall_s: float = 1.0):
    """Engine-loop A/B: the same decode-heavy batch through the synchronous
    loop (``overlap=False``: one blocking fetch, then all host bookkeeping
    before the next dispatch) vs the overlapped loop (``overlap=True``:
    step N+1 speculatively dispatched while step N's bundle is in flight,
    deferred phase pumped on the gap). All requests arrive up front so both
    rows run the identical steady decode the overlap targets — compare
    decode tok/s, token_latency p50/p99, goodput_at_slo, and above all
    host_gap_ms_mean: the fetch->dispatch gap the overlapped loop exists to
    close (speculatively adopted steps contribute zero gap by construction).

    The row self-asserts the loop contract: every request FINISHED, no
    in-flight step or deferred work left behind, zero leaked blocks.
    """
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    mode = "overlap" if overlap else "sync"
    print(f"{label}: {num_requests} requests up front, prompt {prompt_len}, "
          f"max_new {max_new}, engine loop={mode}")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, model.vocab_size,
                           (num_requests, prompt_len)).astype(np.int32)

    engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
        seed=seed, overlap=overlap)

    # warm the compile caches (prefill bucket + decode step) off the clock
    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)
    wid = engine.submit(wprompt, 1)
    engine.run_until_complete()
    del engine.requests[wid]
    engine.metrics = ServingMetrics(engine.profiler, slo_ttft_s=slo_ttft_s,
                                    slo_stall_s=slo_stall_s)

    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new) for p in prompts]
    out = engine.run_until_complete()
    wall = time.perf_counter() - t0

    assert all(engine.requests[r].state.name == "FINISHED" for r in rids)
    assert engine.in_flight is None and not engine._deferred
    assert engine.pool.num_allocated == 0, "leaked KV blocks"
    assert sum(len(out[r]) for r in rids) == num_requests * max_new
    engine.check_invariants()

    s = engine.metrics.summary()
    return report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"host_gap_ms_mean": s["host_gap_ms_mean"],
               "host_gap_ms_p50": s["host_gap_ms_p50"],
               "host_gap_ms_p99": s["host_gap_ms_p99"],
               "token_latency_ms_p50": s["token_latency_ms_p50"],
               "token_latency_ms_p99": s["token_latency_ms_p99"],
               "goodput_at_slo": round(s["goodput_at_slo"], 4),
               "overlap_rebuilds": s["overlap_rebuilds"],
               "steps": s["steps"],
               "requests": s["requests_finished"]})


def _teacher_forced_closeness(model, params, prompts, outs, topk):
    """Teacher-force each prompt + engine output through the plain f32
    forward: mean NLL of the emitted tokens (ppl = exp), top-1 and top-k
    agreement. The teacher always runs the ORIGINAL f32 params — it is the
    quality yardstick every quantized variant is measured against."""
    import jax.numpy as jnp

    seqs = np.stack([np.concatenate([p, o]).astype(np.int32)
                     for p, o in zip(prompts, outs)])
    caches = model.init_cache(len(seqs), seqs.shape[1])
    logits, _ = model.apply_cached(params, jnp.asarray(seqs), caches, 0)
    logits = np.asarray(logits, np.float64)
    plen, n_new = len(prompts[0]), len(outs[0])
    nll, top1, topk_hit, total = 0.0, 0, 0, 0
    for i in range(len(seqs)):
        for j in range(n_new):
            row = logits[i, plen + j - 1]
            row = row - row.max()
            logp = row - np.log(np.exp(row).sum())
            tok = seqs[i, plen + j]
            nll -= logp[tok]
            top1 += int(tok == row.argmax())
            topk_hit += int(tok in np.argsort(row)[-topk:])
            total += 1
    return nll / total, top1 / total, topk_hit / total


def _hbm_fit_concurrent(pool, tokens_per_req, budget_bytes):
    """How many requests' KV fit in a fixed HBM budget — computed from the
    pool's ACTUAL per-token residency (page itemsize + any scale sidecars),
    not an assumed 4 bytes/element, so the int8 rows' capacity win is the
    real one (pages halve, scales claw a little back)."""
    bytes_per_req = (pool.kv_bytes_per_token
                     + pool.kv_scale_bytes_per_token) * tokens_per_req
    return int(budget_bytes // bytes_per_req)


def bench_quant(model, params, *, num_requests: int, prompt_len: int,
                max_new: int, num_blocks: int, block_size: int,
                max_batch_size: int, label: str, variant: str = "f32",
                topk: int = 5, seed: int = 0, slo_ttft_s: float = 2.0,
                kv_budget_mb: int = 1024, shared: dict = None,
                artifact: str = None):
    """Quantized-serving A/B row: the same up-front greedy batch through one
    engine variant — ``f32`` (baseline), ``int8_kv`` (quantized pool), or
    ``int8_kv_w8`` (quantized pool + int8 weights via quant_matmul).

    Quantization trades exactness for bytes, so the quality columns are
    CLOSENESS against the f32 teacher: top-1/top-k agreement of the emitted
    tokens with the teacher-forced f32 argmax, and ppl_delta (teacher-forced
    perplexity of this variant's stream minus the f32 row's). The capacity
    headline is max_concurrent_at_slo: how many requests' KV fit in a fixed
    HBM budget at the pool's actual bytes/token — provided the measured run
    met the TTFT SLO (else 0; capacity you can't serve at SLO is not
    capacity). ``shared`` carries the f32 reference NLL between the three
    rows; ``artifact`` persists all rows as JSON once the last one lands.
    """
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    kv_dtype = "f32" if variant == "f32" else "int8"
    quant_weights = variant == "int8_kv_w8"
    print(f"{label}: {num_requests} requests up front, prompt {prompt_len}, "
          f"max_new {max_new}, kv_dtype={kv_dtype}, "
          f"quant_weights={quant_weights}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).astype(np.int32)
               for _ in range(num_requests)]

    def run_engine(kvd, qw):
        engine = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed, decode_path="paged", kv_dtype=kvd, quant_weights=qw)
        wprompt = np.random.default_rng(seed + 1).integers(
            0, model.vocab_size, prompt_len).astype(np.int32)
        wid = engine.submit(wprompt, 1)
        engine.run_until_complete()
        del engine.requests[wid]
        engine.metrics = ServingMetrics(engine.profiler,
                                        slo_ttft_s=slo_ttft_s)
        t0 = time.perf_counter()
        rids = [engine.submit(p, max_new) for p in prompts]
        out = engine.run_until_complete()
        wall = time.perf_counter() - t0
        assert all(engine.requests[r].state.name == "FINISHED" for r in rids)
        assert engine.pool.num_allocated == 0, "leaked KV blocks"
        engine.check_invariants()
        return engine, [out[r] for r in rids], wall

    engine, outs, wall = run_engine(kv_dtype, quant_weights)
    nll, top1, topk_agree = _teacher_forced_closeness(
        model, params, prompts, outs, topk)

    shared = shared if shared is not None else {}
    if variant == "f32":
        shared["ref_nll"] = nll
    ref_nll = shared.get("ref_nll")
    if ref_nll is None:
        # row isolation: the f32 row failed or was skipped — rebuild the
        # reference off the clock so ppl_delta stays meaningful
        _, ref_outs, _ = run_engine("f32", False)
        ref_nll = _teacher_forced_closeness(
            model, params, prompts, ref_outs, topk)[0]
        shared["ref_nll"] = ref_nll

    s = engine.metrics.summary()
    pool = engine.pool
    met_slo = s["ttft_ms_p99"] <= slo_ttft_s * 1e3
    fit = _hbm_fit_concurrent(pool, prompt_len + max_new,
                              kv_budget_mb * 2**20)
    row = report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"kv_dtype": kv_dtype,
               "quant_weights": int(quant_weights),
               "ttft_ms_p50": s["ttft_ms_p50"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "token_latency_ms_p50": s["token_latency_ms_p50"],
               "token_latency_ms_p99": s["token_latency_ms_p99"],
               "kv_bytes_per_token": pool.kv_bytes_per_token,
               "kv_scale_bytes_per_token": pool.kv_scale_bytes_per_token,
               "top1_agreement": round(top1, 4),
               "topk_agreement": round(topk_agree, 4),
               "ppl": round(float(np.exp(nll)), 4),
               "ppl_delta": round(float(np.exp(nll) - np.exp(ref_nll)), 4),
               "max_concurrent_at_slo": fit if met_slo else 0,
               "goodput_at_slo": round(s["goodput_at_slo"], 4),
               "requests": s["requests_finished"]})
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if artifact and variant == "int8_kv_w8":
            write_artifact(artifact, shared["rows"],
                           meta={"kv_budget_mb": kv_budget_mb},
                           label="quant A/B")
            row["artifact_path"] = artifact
    return row


def bench_tp(model, params, *, num_requests: int, prompt_len: int,
             max_new: int, num_blocks: int, block_size: int,
             max_batch_size: int, label: str, tp: int = 1,
             seed: int = 0, slo_ttft_s: float = 2.0,
             kv_budget_mb: int = 1024, shared: dict = None,
             artifact: str = None):
    """Tensor-parallel A/B row: the same up-front greedy batch through the
    paged engine at ``tp=1`` (baseline) and ``tp>1`` (attention heads and
    the paged KV pool sharded over a TP mesh, one all-reduce per layer).

    TP is an exactness-preserving transform — the only numeric difference
    vs tp=1 is the all-reduce summation order — so unlike the quant rows
    there are no closeness columns: the tp>1 row ASSERTS its streams are
    token-identical to the tp=1 reference (``exact_vs_tp1``). The capacity
    headline is per-chip: each shard holds ``1/tp`` of every page, so
    ``kv_bytes_per_token_per_shard`` divides exactly by tp and
    ``max_concurrent_at_slo`` — requests whose KV fits a fixed PER-CHIP
    HBM budget at the shard's actual residency — rises with it, provided
    the measured run met the TTFT SLO (else 0). ``shared`` carries the
    tp=1 reference streams between rows; ``artifact`` persists all rows
    as JSON once the tp>1 row lands.
    """
    from tnn_tpu.serving import InferenceEngine, ServingMetrics

    print(f"{label}: {num_requests} requests up front, prompt {prompt_len}, "
          f"max_new {max_new}, tp={tp} ({jax.device_count()} devices)")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).astype(np.int32)
               for _ in range(num_requests)]

    def run_engine(degree):
        engine = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed, decode_path="paged", tp=degree)
        wprompt = np.random.default_rng(seed + 1).integers(
            0, model.vocab_size, prompt_len).astype(np.int32)
        wid = engine.submit(wprompt, 1)
        engine.run_until_complete()
        del engine.requests[wid]
        engine.metrics = ServingMetrics(engine.profiler,
                                        slo_ttft_s=slo_ttft_s)
        t0 = time.perf_counter()
        rids = [engine.submit(p, max_new) for p in prompts]
        out = engine.run_until_complete()
        wall = time.perf_counter() - t0
        assert all(engine.requests[r].state.name == "FINISHED" for r in rids)
        assert engine.pool.num_allocated == 0, "leaked KV blocks"
        engine.check_invariants()
        return engine, [out[r] for r in rids], wall

    engine, outs, wall = run_engine(tp)

    shared = shared if shared is not None else {}
    if tp == 1:
        shared["ref_outs"] = outs
    ref_outs = shared.get("ref_outs")
    if ref_outs is None:
        # row isolation: the tp=1 row failed or was skipped — rebuild the
        # reference off the clock so the exactness gate stays meaningful
        _, ref_outs, _ = run_engine(1)
        shared["ref_outs"] = ref_outs
    exact = len(outs) == len(ref_outs) and \
        all(np.array_equal(a, b) for a, b in zip(outs, ref_outs))
    assert exact, "tensor-parallel decode diverged from the tp=1 streams"

    st = engine.stats()
    assert st["tp_degree"] == tp
    pool = engine.pool
    total_bytes = pool.kv_bytes_per_token + pool.kv_scale_bytes_per_token
    per_shard = st["kv_bytes_per_token_per_shard"]
    assert per_shard * tp == total_bytes, \
        "per-shard KV residency is not an exact 1/tp of the pool"

    s = engine.metrics.summary()
    met_slo = s["ttft_ms_p99"] <= slo_ttft_s * 1e3
    fit = int((kv_budget_mb * 2**20) // (per_shard * (prompt_len + max_new)))
    row = report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"tp": tp,
               "ttft_ms_p50": s["ttft_ms_p50"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "token_latency_ms_p50": s["token_latency_ms_p50"],
               "token_latency_ms_p99": s["token_latency_ms_p99"],
               "kv_bytes_per_token_total": total_bytes,
               "kv_bytes_per_token_per_shard": per_shard,
               "exact_vs_tp1": int(exact),
               "max_concurrent_at_slo": fit if met_slo else 0,
               "goodput_at_slo": round(s["goodput_at_slo"], 4),
               "requests": s["requests_finished"]})
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if artifact and tp > 1:
            write_artifact(artifact, shared["rows"],
                           meta={"devices": jax.device_count(),
                                 "kv_budget_mb": kv_budget_mb},
                           label="tp A/B")
            row["artifact_path"] = artifact
    return row


def bench_longctx(model, params, *, sp: int, sp_max: int,
                  blocks_per_chip: int = 4, block_size: int = 4,
                  max_new: int = 4, label: str = "serve_longctx",
                  seed: int = 0, shared: dict = None, artifact: str = None):
    """Sequence-parallel long-context A/B row: the SAME per-chip KV
    footprint (``blocks_per_chip`` pool blocks per device) at sp=1
    (baseline) and sp>1 (each request's blocks round-robined over a
    context mesh, every shard sweeping its own pages, one online-softmax
    merge per layer).

    All gates are deterministic, per the artifact convention that
    wall-clock columns are informational:

    - capacity arithmetic: ``max_context_blocks == sp *
      (blocks_per_chip - 1)`` EXACTLY (one reserved scratch block per
      shard) — aggregate context scales ~N x while per-chip residency
      (``pool_blocks_per_shard``) stays flat;
    - ``exact_vs_sp1``: the short decode batch (fits even the sp=1 pool)
      is token-identical to the sp=1 reference streams;
    - the long-prompt row — KV exceeding ONE chip's pool — serves
      token-exact against the teacher-forced greedy reference at sp>1
      (``gate_long_prompt_exact``) and is REJECTED with a pointed
      admission error, not an OOM or a hang, at sp=1
      (``gate_long_prompt_rejected``);
    - ``gate_shard_span``: each shard's per-layer sweep covers exactly
      ``blocks_per_seq / sp`` table positions — the mechanism behind the
      prefill speedup on a real mesh.

    ``long_prefill_ms`` (the long prompt's TTFT) is reported per sp>1
    row but NOT gated: each shard sweeps 1/sp of the pages per layer, so
    on real multi-chip hardware it drops ~sp x, but this smoke runs on a
    virtual CPU mesh whose shards timeshare one core. ``shared`` carries
    the sp=1 short-batch reference between rows; ``artifact`` persists
    all rows once the sp_max row lands.
    """
    from tnn_tpu.models.gpt2 import generate
    from tnn_tpu.serving import InferenceEngine

    num_blocks = blocks_per_chip * sp
    print(f"{label}: per-chip pool {blocks_per_chip} x {block_size}-token "
          f"blocks, sp={sp} ({jax.device_count()} devices) -> "
          f"{num_blocks} blocks aggregate")

    def mk_engine():
        return InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=2, max_seq_len=model.max_len, seed=seed,
            decode_path="paged", sp=sp)

    # short batch: fits even the sp=1 pool (3 usable blocks = 12 tokens
    # at the defaults), so every row decodes the SAME streams — the
    # token-exactness gate of the sequence-parallel transform. rng(15) is a
    # checked tie-free seed on jax 0.9 (see the long-prompt note below)
    rng = np.random.default_rng(15)
    cap1 = (blocks_per_chip - 1) * block_size
    shorts = [rng.integers(0, model.vocab_size, int(l)).astype(np.int32)
              for l in rng.integers(5, cap1 - max_new + 1, 3)]
    engine = mk_engine()
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new) for p in shorts]
    out = engine.run_until_complete()
    wall = time.perf_counter() - t0
    outs = [out[r] for r in rids]
    assert engine.pool.num_allocated == 0, "leaked KV blocks (short batch)"
    engine.check_invariants()

    shared = shared if shared is not None else {}
    if sp == 1:
        shared["ref_outs"] = outs
    ref_outs = shared.get("ref_outs")
    exact = ref_outs is not None and len(outs) == len(ref_outs) and \
        all(np.array_equal(a, b) for a, b in zip(outs, ref_outs))
    assert exact, "sequence-parallel decode diverged from the sp=1 streams"

    st = engine.stats()
    assert st["sp_degree"] == sp
    assert st["pool_blocks_per_shard"] == blocks_per_chip, \
        "per-chip residency moved — the capacity headline is flat HBM"
    max_ctx_blocks = engine.pool.capacity
    assert max_ctx_blocks == sp * (blocks_per_chip - 1), \
        "aggregate context capacity is not exactly ~N x per chip"
    assert engine.blocks_per_seq % sp == 0
    span = engine.blocks_per_seq // sp

    # long-prompt row: KV needs more blocks than ONE chip's pool holds.
    # Sized to the row's own aggregate capacity, so the sp=4 row serves a
    # prompt more than 3 x what any single chip could. rng(104) is a
    # checked tie-free seed: the merge is exact to float tolerance, but
    # XLA fusion drift inside shard_map can flip greedy argmax near-ties
    # on this tiny random model (same convention as the tp/sp tests).
    long_len = max_ctx_blocks * block_size - max_new
    long_p = np.random.default_rng(104).integers(
        0, model.vocab_size, long_len).astype(np.int32)
    long_exact = 0
    long_rejected = 0
    long_ttft_ms = 0.0
    if sp == 1:
        try:
            # the NEXT row's long prompt (same per-chip footprint, sp x
            # the aggregate) must fail cleanly here at admission
            probe = np.random.default_rng(104).integers(
                0, model.vocab_size,
                2 * (blocks_per_chip - 1) * block_size - max_new
            ).astype(np.int32)
            engine.submit(probe, max_new)
        except ValueError:
            long_rejected = 1
        assert long_rejected, \
            "a prompt exceeding one chip's pool was admitted at sp=1"
    else:
        eng2 = mk_engine()
        r = eng2.submit(long_p, max_new)
        t0 = time.perf_counter()
        lout = eng2.run_until_complete()
        long_prefill_s = time.perf_counter() - t0
        s2 = eng2.metrics.summary()
        long_ttft_ms = s2["ttft_ms_p50"] or long_prefill_s * 1e3
        ref = np.asarray(generate(model, params, long_p[None], max_new,
                                  max_len=eng2.assembly_len))[0].tolist()
        long_exact = int(lout[r] == ref)
        assert long_exact, \
            "long-prompt stream diverged from the greedy reference"
        assert eng2.pool.num_allocated == 0, "leaked KV blocks (long row)"
        eng2.check_invariants()

    s = engine.metrics.summary()
    row = report(
        label, wall, items=s["decode_tokens"], item_name="tok",
        extra={"sp": sp,
               "num_blocks": num_blocks,
               # "blocks_per_chip", not "...per_shard": the _per_s info
               # marker would misfile this structural field as a rate
               "blocks_per_chip": blocks_per_chip,
               "max_context_blocks": max_ctx_blocks,
               "max_context_tokens": max_ctx_blocks * block_size,
               "shard_table_span": span,
               "gate_shard_span": int(span * sp == engine.blocks_per_seq),
               "exact_vs_sp1": int(exact),
               "long_prompt_len": long_len if sp > 1 else 0,
               "gate_long_prompt_exact": long_exact,
               "gate_long_prompt_rejected": long_rejected,
               "long_prefill_ms": round(long_ttft_ms, 3),
               "ttft_ms_p50": s["ttft_ms_p50"],
               "ttft_ms_p99": s["ttft_ms_p99"],
               "requests": s["requests_finished"]})
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if artifact and sp == sp_max:
            write_artifact(artifact, shared["rows"],
                           meta={"devices": jax.device_count(),
                                 "blocks_per_chip": blocks_per_chip,
                                 "block_size": block_size},
                           label="longctx A/B")
            row["artifact_path"] = artifact
    return row


def bench_availability(model, params, *, replicas: int, num_requests: int,
                       rate_per_s: float, prompt_len: int, max_new: int,
                       num_blocks: int, block_size: int, max_batch_size: int,
                       label: str, kill: bool, kill_after: int = 0,
                       check_exact: bool = True, seed: int = 0,
                       slo_ttft_s: float = 2.0):
    """Replicated-availability row: one Poisson trace through a ``Router``
    over ``replicas`` supervised engines. With ``kill`` set, the busiest
    replica is hard-killed mid-run (after ``kill_after`` submissions) — its
    in-flight streams fail over and resume token-exact on the survivors.
    Run once with ``kill=False`` and once with ``kill=True`` on the same
    trace: the delta in goodput_at_slo and ttft_ms_p99 between the twin rows
    IS the cost of losing 1 of N replicas mid-run.

    Goodput and TTFT are computed at the bench level from the router's
    ``done`` events (not engine metrics): a migrated request's TTFT spans
    replicas, which only the router-side clock sees. The row self-asserts
    the failover contract — exactly one terminal per request, every request
    FINISHED, migrated streams byte-identical to a single-engine reference
    (``check_exact``), survivor pools zero-leak, clean exit-0 drain.
    """
    import threading

    from tnn_tpu.serving import (EngineSupervisor, InferenceEngine, Router,
                                 ServingMetrics, SupervisorState)

    kill_after = kill_after or num_requests // 2
    print(f"{label}: {num_requests} requests @ ~{rate_per_s}/s across "
          f"{replicas} replicas"
          + (f", killing the busiest after {kill_after} submits" if kill
             else " (unkilled baseline)"))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).astype(np.int32)
               for _ in range(num_requests)]
    gaps = rng.exponential(1.0 / rate_per_s, num_requests)

    ref = None
    if check_exact:
        # single-engine greedy reference: outputs are batch-independent, so
        # a migrated stream reassembled across two replicas must match it
        ref_engine = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed)
        ref = []
        for p in prompts:
            rid = ref_engine.submit(p, max_new)
            ref.append(ref_engine.run_until_complete()[rid])

    # dedicated warmup prompt per replica (same rationale as bench_load:
    # a trace prompt in the prefix cache would hand one timed request a
    # free hit), then reset metrics so the timed window starts clean
    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)

    def mk_engine():
        eng = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed)
        wid = eng.submit(wprompt, 1)
        eng.run_until_complete()
        del eng.requests[wid]
        eng.metrics = ServingMetrics(eng.profiler, slo_ttft_s=slo_ttft_s)
        return eng

    engines = [mk_engine() for _ in range(replicas)]
    sups = [EngineSupervisor(e, max_restarts=3, restart_backoff_s=0.0,
                             drain_deadline_s=60.0) for e in engines]
    router = Router(sups, seed=seed)

    lock = threading.Lock()
    terminals = {}   # gid -> terminal event count (exactly-once gate)
    done = {}        # gid -> done event (tokens, ttft_ms)

    def mk_listener():
        def listener(ev):
            if ev["event"] == "token":
                return
            with lock:
                terminals[ev["id"]] = terminals.get(ev["id"], 0) + 1
                if ev["event"] == "done":
                    done[ev["id"]] = ev
        return listener

    t0 = time.perf_counter()
    router.start()
    victim = None
    gids = []
    for i, (p, gap) in enumerate(zip(prompts, gaps)):
        time.sleep(float(gap))
        gids.append(router.submit(p, max_new, listener=mk_listener()))
        if kill and victim is None and i + 1 >= kill_after:
            # pick the busiest replica WITH live streams — killing an idle
            # one would prove nothing about mid-stream migration
            for _ in range(400):
                live = [r for r in router.stats()["replicas"]
                        if not r["killed"] and r["live_requests"] > 0]
                if live:
                    victim = max(live,
                                 key=lambda r: r["live_requests"])["replica"]
                    break
                time.sleep(0.005)
            assert victim is not None, \
                "no in-flight stream to interrupt — workload too light"
            router.kill_replica(victim)
    deadline = time.monotonic() + 120.0
    while sum(terminals.values()) < len(gids):
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"availability bench wedged: "
                f"{sum(terminals.values())}/{len(gids)} terminal")
        time.sleep(0.01)
    hg = router.health_gauges()
    st = router.stats()
    router.request_drain("bench complete")
    if not router.join(timeout=60):
        raise RuntimeError("router failed to drain")
    wall = time.perf_counter() - t0

    # the failover contract IS the gate
    assert router.state is SupervisorState.STOPPED and router.exit_code == 0
    assert all(terminals.get(g, 0) == 1 for g in gids), \
        "duplicated or missing terminal events"
    assert len(done) == len(gids), \
        f"only {len(done)}/{len(gids)} requests FINISHED"
    exact = -1
    if check_exact:
        exact = int(all(done[g]["tokens"] == ref[i]
                        for i, g in enumerate(gids)))
        assert exact, "a failed-over stream diverged from the reference"
    if kill:
        assert st["migrated_requests"] >= 1, \
            "the kill interrupted nothing — no stream migrated"
    else:
        assert st["migrated_requests"] == 0
    for i, eng in enumerate(engines):
        if kill and i == victim:
            continue  # the killed replica's pool died with it
        assert eng.pool.num_allocated == 0, f"survivor {i} leaked KV blocks"
        eng.check_invariants()

    ttfts = np.array([done[g]["ttft_ms"] for g in gids], dtype=float)
    within = int(np.sum(ttfts <= slo_ttft_s * 1e3))
    return report(
        label, wall, items=num_requests, item_name="req",
        extra={"requests": num_requests,
               "replicas": replicas,
               "killed_replica": int(victim) if kill else -1,
               "finished": len(done),
               "goodput_at_slo": round(within / wall, 4),
               "slo_ttft_s": slo_ttft_s,
               "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3),
               "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3),
               "migrated_requests": st["migrated_requests"],
               "migration_resume_tokens": st["migration_resume_tokens"],
               "router_retries": st["router_retries"],
               "replica_restarts": st["replica_restarts"],
               "replicas_healthy": hg["replicas_healthy"],
               "exact_vs_ref": exact,
               "terminal": int(sum(terminals.values()))})


def bench_straggler(model, params, *, replicas: int, num_requests: int,
                    rate_per_s: float, prompt_len: int, max_new: int,
                    num_blocks: int, block_size: int, max_batch_size: int,
                    label: str, mitigate: bool, slow_idx: int = 0,
                    slow_step_s: float = 0.4, hedge_ttft_s: float = 0.08,
                    hedge_budget: float = 0.5, degrade_factor: float = 1.5,
                    check_exact: bool = True, seed: int = 0,
                    slo_ttft_s: float = 0.25, shared=None, artifact=None):
    """Gray-failure A/B row: one Poisson trace through a ``Router`` over
    ``replicas`` engines where replica ``slow_idx`` is PERSISTENTLY slow
    (``slow_step_s`` injected per engine step) — alive, token-correct,
    breaker-invisible. Run once with ``mitigate=False`` (hedging and
    ejection off: pure JSQ keeps feeding the straggler) and once with
    ``mitigate=True`` (TTFT hedging + health-scored ejection + proactive
    migration): the ttft_ms_p99 / goodput_at_slo delta between the twin
    rows IS the value of gray-failure tolerance.

    The row self-asserts the contract — exactly one terminal per request,
    every request FINISHED, streams byte-identical to a single-engine
    greedy reference (hedge winners and proactively migrated streams
    included), hedges within budget, zero leaked blocks, clean exit-0
    drain. With ``shared``, the mitigated row additionally asserts its
    p99 TTFT beats the unmitigated twin's and persists both rows as one
    JSON artifact."""
    import threading

    from tnn_tpu.serving import (EngineSupervisor, InferenceEngine, Router,
                                 ServingMetrics, SupervisorState)

    print(f"{label}: {num_requests} requests @ ~{rate_per_s}/s across "
          f"{replicas} replicas, replica {slow_idx} slowed by "
          f"{slow_step_s}s/step, mitigation "
          + ("ON (hedge+eject)" if mitigate else "OFF (pure JSQ)"))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).astype(np.int32)
               for _ in range(num_requests)]
    gaps = rng.exponential(1.0 / rate_per_s, num_requests)

    ref = None
    if check_exact:
        # single-engine greedy reference: outputs are batch-independent,
        # so a hedged or proactively migrated stream must match it
        ref_engine = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed)
        ref = []
        for p in prompts:
            rid = ref_engine.submit(p, max_new)
            ref.append(ref_engine.run_until_complete()[rid])

    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)

    def mk_engine():
        # max_new=2 warms BOTH the prefill and the decode step: a decode
        # compile spike during the timed window would poison the health
        # score's step-latency EWMA and eject a healthy replica
        eng = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed)
        wid = eng.submit(wprompt, 2)
        eng.run_until_complete()
        del eng.requests[wid]
        eng.metrics = ServingMetrics(eng.profiler, slo_ttft_s=slo_ttft_s)
        return eng

    engines = [mk_engine() for _ in range(replicas)]
    sups = [EngineSupervisor(e, max_restarts=3, restart_backoff_s=0.0,
                             drain_deadline_s=60.0) for e in engines]
    router = Router(
        sups, seed=seed,
        # fixed hedge threshold (not adaptive): the A/B must not depend
        # on how many TTFT samples landed before the straggler bites
        hedge_ttft_s=hedge_ttft_s if mitigate else None,
        hedge_budget=hedge_budget if mitigate else 0.0,
        degrade_factor=degrade_factor if mitigate else 0.0,
        # a window longer than the hedge threshold: overdue first tokens
        # hedge FIRST (fast rescue), then the sustained-slow replica is
        # ejected and its remaining streams proactively migrate
        degrade_window_s=max(0.25, 3 * hedge_ttft_s),
        # keep the straggler ejected for the whole row: it never speeds
        # back up, so recovery probes would only re-strand requests
        degrade_cooldown_s=60.0)
    # the gray failure itself: alive, correct, just slow — applied before
    # any submit so both rows see the same degraded fleet from t=0
    router.slow_replica(slow_idx, slow_step_s)

    lock = threading.Lock()
    terminals = {}   # gid -> terminal event count (exactly-once gate)
    done = {}        # gid -> done event (tokens, ttft_ms)

    def mk_listener():
        def listener(ev):
            if ev["event"] == "token":
                return
            with lock:
                terminals[ev["id"]] = terminals.get(ev["id"], 0) + 1
                if ev["event"] == "done":
                    done[ev["id"]] = ev
        return listener

    t0 = time.perf_counter()
    router.start()
    gids = []
    for p, gap in zip(prompts, gaps):
        time.sleep(float(gap))
        gids.append(router.submit(p, max_new, listener=mk_listener()))
    deadline = time.monotonic() + 120.0
    while sum(terminals.values()) < len(gids):
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"straggler bench wedged: "
                f"{sum(terminals.values())}/{len(gids)} terminal")
        time.sleep(0.01)
    st = router.stats()
    router.request_drain("bench complete")
    if not router.join(timeout=60):
        raise RuntimeError("router failed to drain")
    wall = time.perf_counter() - t0

    # the gray-failure contract IS the gate
    assert router.state is SupervisorState.STOPPED and router.exit_code == 0
    assert all(terminals.get(g, 0) == 1 for g in gids), \
        "duplicated or missing terminal events"
    assert len(done) == len(gids), \
        f"only {len(done)}/{len(gids)} requests FINISHED"
    exact = -1
    if check_exact:
        exact = int(all(done[g]["tokens"] == ref[i]
                        for i, g in enumerate(gids)))
        assert exact, "a hedged/migrated stream diverged from the reference"
    hedge_cap = max(1, int(hedge_budget * num_requests))
    if mitigate:
        assert (st["hedges_fired"] + st["degraded_ejections"]
                + st["proactive_migrations"]) >= 1, \
            "mitigation never engaged — straggler too mild for the knobs"
        assert st["hedges_fired"] <= hedge_cap, \
            f"hedge amplification: {st['hedges_fired']} > cap {hedge_cap}"
        assert st["hedges_won"] <= st["hedges_fired"]
        assert st["hedges_cancelled"] <= st["hedges_fired"]
    else:
        assert st["hedges_fired"] == 0 and st["degraded_ejections"] == 0 \
            and st["proactive_migrations"] == 0, \
            "mitigation fired with hedging and ejection disabled"
    for i, eng in enumerate(engines):
        assert eng.pool.num_allocated == 0, f"replica {i} leaked KV blocks"
        eng.check_invariants()

    ttfts = np.array([done[g]["ttft_ms"] for g in gids], dtype=float)
    within = int(np.sum(ttfts <= slo_ttft_s * 1e3))
    row = report(
        label, wall, items=num_requests, item_name="req",
        extra={"requests": num_requests,
               "replicas": replicas,
               "slow_replica": slow_idx,
               "slow_step_s": slow_step_s,
               "mitigate": int(mitigate),
               "finished": len(done),
               "goodput_at_slo": round(within / wall, 4),
               "slo_ttft_s": slo_ttft_s,
               "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3),
               "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3),
               "hedges_fired": st["hedges_fired"],
               "hedges_won": st["hedges_won"],
               "hedges_cancelled": st["hedges_cancelled"],
               "degraded_ejections": st["degraded_ejections"],
               "proactive_migrations": st["proactive_migrations"],
               "migrated_requests": st["migrated_requests"],
               "router_retries": st["router_retries"],
               "exact_vs_ref": exact,
               "terminal": int(sum(terminals.values()))})
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if mitigate:
            off = [r for r in shared["rows"] if not r.get("mitigate")]
            if off:
                assert row["ttft_ms_p99"] < off[0]["ttft_ms_p99"], \
                    (f"mitigation did not improve tail TTFT: "
                     f"{row['ttft_ms_p99']} >= {off[0]['ttft_ms_p99']}")
            if artifact:
                write_artifact(artifact, shared["rows"],
                               label="straggler A/B")
                row["artifact_path"] = artifact
    return row


def _tier_probe(model, params, *, num_blocks=10, block_size=4,
                tier_bytes=1 << 20, seed=0):
    """Deterministic host-tier hit-rate probe on a working set larger than
    the device pool: six prompts sharing an 8-token (two-block) prefix run
    serially TWICE through a pool too small to keep the set resident — the
    second pass's prefix probes re-admit demoted blocks from the host tier.
    The no-tier baseline runs the identical trace with the tier disabled
    (hit rate zero by construction) and must produce identical tokens."""
    from tnn_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, model.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, model.vocab_size, 4).astype(np.int32)]) for _ in range(6)]

    def run(tier_on):
        eng = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=2, chunk_size=8, chunked_prefill=True,
            prefix_cache=True, decode_path="paged", seed=seed,
            host_tier_bytes=tier_bytes if tier_on else 0)
        toks = []
        for _ in range(2):
            for p in prompts:
                rid = eng.submit(p, 6)
                toks.append(eng.run_until_complete()[rid])
        st = eng.stats()
        assert eng.pool.num_allocated == 0
        eng.check_invariants()
        return toks, st

    on_toks, on_st = run(True)
    off_toks, off_st = run(False)
    assert on_toks == off_toks, "tier-on streams diverged from tier-off"
    assert off_st["tier_readmits"] == 0
    return {"tier_probe_hits": int(on_st["tier_readmits"]),
            "tier_probe_demotions": int(on_st["tier_demotions"]),
            "tier_probe_hit_rate": round(
                on_st["tier_readmits"] / max(1, on_st["tier_demotions"]), 4),
            "tier_probe_baseline_hits": int(off_st["tier_readmits"])}


def bench_spike(model, params, *, num_requests: int, prompt_len: int,
                max_new: int, num_blocks: int, block_size: int,
                max_batch_size: int, autoscale: bool, max_replicas: int = 3,
                tier_bytes: int = 1 << 20, max_queue_depth: int = 10,
                burst_rate_per_s: float = 200.0, trickle_rate_per_s: float = 20.0,
                step_delay_s: float = 0.02, slo_ttft_s: float = 0.25,
                label: str = "serve_spike",
                seed: int = 0, shared=None, artifact=None):
    """Elastic-fleet A/B row: a two-phase arrival trace (gentle trickle,
    then a Poisson burst) through a ``Router`` whose replicas all carry the
    host-RAM KV tier, run once pinned at a single replica (``autoscale``
    False) and once under the load-driven :class:`Autoscaler` (scale up
    under the burst from a warm-standby pool, hysteresis-guarded zero-loss
    scale-down after it). The goodput_at_slo / rejected delta between the
    twin rows is the measured value of elasticity; replicas_timeline
    records the fleet size the controller actually actuated.

    Standbys are pre-built and warmed (a real fleet joins from warm images,
    and an in-row cold compile would charge XLA time to the controller), so
    a join is pure control-plane latency. The row self-asserts the
    resilience contract — exactly one terminal per accepted request, every
    accepted request FINISHED token-exact vs a single-engine greedy
    reference, zero leaked blocks in every replica's device pool AND host
    tier — plus, with ``shared``, that the on row's goodput strictly beats
    the off twin's and the deterministic tier probe (see
    :func:`_tier_probe`) readmitted at least one block where the no-tier
    baseline by construction readmits none."""
    import threading

    from tnn_tpu.serving import (AdmissionRejected, Autoscaler,
                                 EngineSupervisor, FaultPlan,
                                 InferenceEngine, Router, ServingMetrics,
                                 ShuttingDown, SupervisorState)

    print(f"{label}: {num_requests} requests (trickle ~{trickle_rate_per_s}"
          f"/s then burst ~{burst_rate_per_s}/s), autoscaler "
          + (f"ON (1..{max_replicas} replicas)" if autoscale
             else "OFF (pinned at 1 replica)"))
    rng = np.random.default_rng(seed)
    # grouped prompts: shared two-block prefixes drive the prefix cache /
    # host tier during the run itself (working set > one replica's pool)
    n_groups = 4
    prefixes = [rng.integers(0, model.vocab_size,
                             2 * block_size).astype(np.int32)
                for _ in range(n_groups)]
    prompts = [np.concatenate([prefixes[i % n_groups], rng.integers(
        0, model.vocab_size,
        prompt_len - 2 * block_size).astype(np.int32)])
        for i in range(num_requests)]
    n_trickle = max(1, num_requests // 4)
    gaps = np.concatenate([
        rng.exponential(1.0 / trickle_rate_per_s, n_trickle),
        rng.exponential(1.0 / burst_rate_per_s, num_requests - n_trickle)])

    ref_engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
        seed=seed)
    ref = []
    for p in prompts:
        rid = ref_engine.submit(p, max_new)
        ref.append(ref_engine.run_until_complete()[rid])

    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, prompt_len).astype(np.int32)

    def mk_engine():
        eng = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            chunk_size=8, chunked_prefill=True, prefix_cache=True,
            max_queue_depth=max_queue_depth, host_tier_bytes=tier_bytes,
            seed=seed)
        wid = eng.submit(wprompt, 2)
        eng.run_until_complete()
        del eng.requests[wid]
        eng.kv_tier.clear()
        eng.metrics = ServingMetrics(eng.profiler, slo_ttft_s=slo_ttft_s)
        # uniform injected step latency: a tiny smoke model decodes in
        # microseconds, which would let ONE replica absorb any burst and
        # reduce the A/B to wall-clock noise; a realistic per-step cost
        # makes the single-replica row genuinely saturate so elasticity
        # (not machine speed) is what the twin rows measure
        if step_delay_s > 0:
            eng.faults = FaultPlan()
            eng.faults.step_delay_s = float(step_delay_s)
        return eng

    engines = [mk_engine() for _ in range(max_replicas if autoscale else 1)]
    sups = [EngineSupervisor(e, max_restarts=3, restart_backoff_s=0.0,
                             drain_deadline_s=60.0) for e in engines]
    standbys = list(sups[1:])

    def factory():
        if not standbys:
            raise ConnectionError("warm-standby pool exhausted")
        return standbys.pop(0)

    router = Router([sups[0]], seed=seed)
    scaler = Autoscaler(
        router, factory, min_replicas=1, max_replicas=max_replicas,
        up_load=2.0, down_load=0.75, hysteresis_s=0.1, cooldown_s=0.05,
        interval_s=0.02) if autoscale else None

    lock = threading.Lock()
    terminals = {}   # gid -> terminal event count (exactly-once gate)
    done = {}        # gid -> done event (tokens, ttft_ms)

    def mk_listener():
        def listener(ev):
            if ev["event"] == "token":
                return
            with lock:
                terminals[ev["id"]] = terminals.get(ev["id"], 0) + 1
                if ev["event"] == "done":
                    done[ev["id"]] = ev
        return listener

    t0 = time.perf_counter()
    timeline = [(0.0, 1)]   # (elapsed_s, active_replicas) on change

    def sample_replicas():
        n = router.num_active_replicas()
        if n != timeline[-1][1]:
            timeline.append((round(time.perf_counter() - t0, 4), n))

    router.start()
    if scaler is not None:
        scaler.start()
    gids, owner, rejected = [], {}, 0
    for i, (p, gap) in enumerate(zip(prompts, gaps)):
        time.sleep(float(gap))
        try:
            g = router.submit(p, max_new, listener=mk_listener())
        except (AdmissionRejected, ShuttingDown):
            rejected += 1
        else:
            gids.append(g)
            owner[g] = i
        sample_replicas()
    deadline = time.monotonic() + 120.0
    while True:
        with lock:
            if sum(terminals.values()) >= len(gids):
                break
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"spike bench wedged: {sum(terminals.values())}"
                f"/{len(gids)} terminal")
        sample_replicas()
        time.sleep(0.01)
    # serving wall: last terminal in — goodput must not be diluted by the
    # post-run scale-down grace or the drain
    wall = time.perf_counter() - t0
    if scaler is not None:
        # quiet grace: give the controller its hysteresis window so the
        # now-idle fleet shrinks back (a zero-stream retire is the
        # trivially zero-loss scale-down) and the timeline records it
        grace = time.monotonic() + 2.0
        while time.monotonic() < grace:
            sample_replicas()
            if (scaler.stats()["scale_downs"] > 0
                    and router.num_active_replicas() <= 1):
                break
            time.sleep(0.02)
        sample_replicas()
        scaler.stop()
    replicas_max = max(n for _, n in timeline)
    st = router.stats()
    scaler_st = scaler.stats() if scaler is not None else {}
    router.request_drain("bench complete")
    if not router.join(timeout=60):
        raise RuntimeError("router failed to drain")

    # the elasticity contract IS the gate
    assert router.state is SupervisorState.STOPPED and router.exit_code == 0
    assert all(terminals.get(g, 0) == 1 for g in gids), \
        "duplicated or missing terminal events"
    assert len(done) == len(gids), \
        f"only {len(done)}/{len(gids)} accepted requests FINISHED"
    exact = int(all(done[g]["tokens"] == ref[owner[g]] for g in gids))
    assert exact, "a migrated/tiered stream diverged from the reference"
    tier_hits = tier_demotions = 0
    for i, eng in enumerate(engines):
        assert eng.pool.num_allocated == 0, f"replica {i} leaked KV blocks"
        eng.check_invariants()   # device pool AND host tier accounting
        ts = eng.kv_tier.stats()
        tier_hits += ts["tier_readmits"]
        tier_demotions += ts["tier_demotions"]

    probe = None
    if shared is not None:
        if "tier_probe" not in shared:
            shared["tier_probe"] = _tier_probe(model, params, seed=seed)
        probe = shared["tier_probe"]

    ttfts = np.array([done[g]["ttft_ms"] for g in gids], dtype=float)
    within = int(np.sum(ttfts <= slo_ttft_s * 1e3))
    row = report(
        label, wall, items=len(gids), item_name="req",
        extra={"requests": num_requests,
               "accepted": len(gids),
               "rejected": rejected,
               "finished": len(done),
               "autoscale": int(autoscale),
               "goodput_at_slo": round(within / wall, 4),
               "slo_ttft_s": slo_ttft_s,
               "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3),
               "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3),
               "replicas_max": replicas_max,
               "replicas_timeline": [[t, n] for t, n in timeline],
               "scale_ups": scaler_st.get("scale_ups", 0),
               "scale_downs": scaler_st.get("scale_downs", 0),
               "join_failures": scaler_st.get("join_failures", 0),
               "tier_hits": tier_hits,
               "tier_demotions": tier_demotions,
               "migrated_requests": st["migrated_requests"],
               "proactive_migrations": st["proactive_migrations"],
               "exact_vs_ref": exact,
               "terminal": int(sum(terminals.values()))})
    if probe is not None:
        row.update(probe)
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if autoscale:
            off = [r for r in shared["rows"] if not r.get("autoscale")]
            if off:
                assert row["goodput_at_slo"] > off[0]["goodput_at_slo"], \
                    (f"autoscaler did not improve goodput-at-SLO: "
                     f"{row['goodput_at_slo']} <= "
                     f"{off[0]['goodput_at_slo']}")
            assert row["replicas_max"] > 1, "autoscaler never scaled up"
            assert row["tier_probe_hits"] > row["tier_probe_baseline_hits"],\
                "host tier readmitted nothing on a >HBM working set"
            if artifact:
                write_artifact(artifact, shared["rows"], label="spike A/B")
                row["artifact_path"] = artifact
    return row


def _handoff_probe(model, params, *, seed=0):
    """Deterministic KV-handoff cost probe: ONE long prompt through a
    synchronous 2-replica prefill/decode fleet, once with real KV-block
    handoff and once degraded to recompute-resume (``handoff_kv=False``).
    Both runs hand off at the same first-token boundary and must produce
    tokens identical to a single-engine reference; the receiver-side
    prefill work is counted exactly (chunks processed, prompt positions
    admitted straight from adopted KV), so "handoff strictly cheaper than
    recompute" is a deterministic counter comparison, not a timing race."""
    from tnn_tpu.serving import EngineSupervisor, InferenceEngine, Router

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, model.vocab_size, 40).astype(np.int32)

    ref_eng = InferenceEngine(model, params, num_blocks=64, block_size=4,
                              max_batch_size=4, max_seq_len=64, seed=seed)
    ref_rid = ref_eng.submit(prompt, 8)
    ref = ref_eng.run_until_complete()[ref_rid]

    def run(kv):
        engines = [InferenceEngine(
            model, params, num_blocks=64, block_size=4, max_batch_size=4,
            max_seq_len=64, chunk_size=8, chunked_prefill=True,
            prefix_cache=True, decode_path="paged", seed=seed)
            for _ in range(2)]
        sups = [EngineSupervisor(e, restart_backoff_s=0.0) for e in engines]
        router = Router(sups, seed=seed, roles=["prefill", "decode"],
                        disagg_prompt_threshold=16, handoff_kv=kv)
        out = {}

        def listener(ev):
            if ev["event"] == "done":
                out["tokens"] = ev["tokens"]

        router.submit(prompt, 8, listener=listener)
        router.run_sync()
        assert router.stats()["boundary_handoffs"] == 1, \
            "probe request never crossed the prefill->decode boundary"
        recv = engines[1].metrics.summary()
        for i, e in enumerate(engines):
            assert e.pool.num_allocated == 0, f"probe replica {i} leaked"
            e.check_invariants()
        return out["tokens"], recv

    kv_toks, kv_recv = run(True)
    rc_toks, rc_recv = run(False)
    assert kv_toks == ref and rc_toks == ref, \
        "handoff probe streams diverged from the single-engine reference"
    cheaper = (kv_recv["prefill_chunks"] < rc_recv["prefill_chunks"]
               and kv_recv["prefill_tokens_saved"]
               > rc_recv["prefill_tokens_saved"])
    assert cheaper, (
        f"KV handoff not strictly cheaper than recompute-resume: receiver "
        f"chunks {kv_recv['prefill_chunks']} vs {rc_recv['prefill_chunks']}, "
        f"tokens from adopted KV {kv_recv['prefill_tokens_saved']} vs "
        f"{rc_recv['prefill_tokens_saved']}")
    return {"handoff_probe_recv_chunks_kv": int(kv_recv["prefill_chunks"]),
            "handoff_probe_recv_chunks_recompute":
                int(rc_recv["prefill_chunks"]),
            "handoff_probe_tokens_from_kv":
                int(kv_recv["prefill_tokens_saved"]),
            "gate_handoff_cheaper": int(cheaper)}


def _fleet_prefix_probe(model, params, *, seed=0):
    """Deterministic fleet-prefix-cache probe. A 12-token "system prompt"
    request runs wholly on the prefill replica (max_new=1, so it never
    crosses the boundary) and publishes the shared two-block prefix there;
    three 11-token requests sharing the same prefix then land on the decode
    replica (below the disagg threshold). Directory off, the decode
    replica's first request cold-misses and recomputes the prefix;
    directory on, the router pulls the publisher's blocks across, so the
    aggregate fleet hit count is strictly higher on an otherwise identical,
    token-exact trace."""
    from tnn_tpu.serving import EngineSupervisor, InferenceEngine, Router

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, model.vocab_size, 8).astype(np.int32)
    sys_prompt = np.concatenate(
        [prefix, rng.integers(0, model.vocab_size, 4).astype(np.int32)])
    shorts = [np.concatenate([prefix, rng.integers(
        0, model.vocab_size, 3).astype(np.int32)]) for _ in range(3)]

    ref_eng = InferenceEngine(model, params, num_blocks=64, block_size=4,
                              max_batch_size=4, max_seq_len=32, seed=seed)
    refs = []
    for p, n in [(sys_prompt, 1)] + [(p, 4) for p in shorts]:
        rid = ref_eng.submit(p, n)
        refs.append(ref_eng.run_until_complete()[rid])

    def run(fleet):
        engines = [InferenceEngine(
            model, params, num_blocks=64, block_size=4, max_batch_size=4,
            max_seq_len=32, chunk_size=8, chunked_prefill=True,
            prefix_cache=True, decode_path="paged", seed=seed)
            for _ in range(2)]
        sups = [EngineSupervisor(e, restart_backoff_s=0.0) for e in engines]
        router = Router(sups, seed=seed, roles=["prefill", "decode"],
                        disagg_prompt_threshold=12, fleet_prefix=fleet)
        toks = []
        for p, n in [(sys_prompt, 1)] + [(p, 4) for p in shorts]:
            out = {}

            def listener(ev, out=out):
                if ev["event"] == "done":
                    out["tokens"] = ev["tokens"]

            router.submit(p, n, listener=listener)
            router.run_sync()
            toks.append(out["tokens"])
            # the monitor thread owns directory refreshes in a live fleet;
            # the sync probe drives them by hand between requests
            router._refresh_prefix_dir()
        hits = sum(e.metrics.summary()["prefix_hits"] for e in engines)
        pulls = router.stats()["fleet_prefix_pulls"]
        for i, e in enumerate(engines):
            assert e.pool.num_allocated == 0, f"probe replica {i} leaked"
            e.check_invariants()
        return toks, hits, pulls

    on_toks, on_hits, on_pulls = run(True)
    off_toks, off_hits, off_pulls = run(False)
    assert on_toks == refs and off_toks == refs, \
        "fleet prefix probe streams diverged from the reference"
    assert off_pulls == 0
    assert on_pulls >= 1, "fleet prefix directory never pulled a block"
    assert on_hits > off_hits, (
        f"fleet prefix cache did not beat the per-replica baseline: "
        f"{on_hits} hits vs {off_hits}")
    return {"fleet_probe_hits": int(on_hits),
            "fleet_probe_baseline_hits": int(off_hits),
            "fleet_probe_pulls": int(on_pulls),
            "gate_fleet_hit_rate": int(on_hits > off_hits)}


def bench_disagg(model, params, *, variant: str, n_long: int = 6,
                 n_chat: int = 12, long_len: int = 40, max_new_long: int = 6,
                 max_new_chat: int = 8, num_blocks: int = 64,
                 block_size: int = 4, max_batch_size: int = 6,
                 chunk_size: int = 32, step_delay_s: float = 0.004,
                 prefill_delay_per_token_s: float = 0.02,
                 gap_s: float = 0.012, slo_ttft_s: float = 0.5,
                 label: str = "serve_disagg", seed: int = 0,
                 shared=None, artifact=None):
    """Disaggregated-serving A/B row: a long-prompt + short-chat mix through
    a 3-replica ``Router``, once all-mixed (``variant="mixed"``), once with
    static prefill/decode roles but handoff degraded to recompute-resume
    (``"recompute"``), and once with real KV-block handoff plus the
    fleet-wide prefix directory (``"kv"``).

    Engines charge prefill a per-token cost (``prefill_delay_per_token_s``,
    the same realistic-cost trick as bench_spike's ``step_delay_s``), so a
    long prefill chunk genuinely stalls whatever decodes share its step. In
    the mixed fleet every replica interleaves long prefills with chat
    decodes; with roles, chat requests land on decode replicas and long
    prompts hand off at the first-token boundary, so chat TTFT p99 and
    decode-stall p99 improve — the "kv" row asserts both against the mixed
    twin. Every row asserts the correctness contract: exactly one terminal
    per request, all requests FINISHED token-exact vs a single-engine
    reference, boundary handoffs fired for every long prompt in the disagg
    rows, and zero leaked blocks in every replica's pool. The "kv" row adds
    the two deterministic probes (:func:`_handoff_probe` — handoff strictly
    cheaper than recompute on the receiver; :func:`_fleet_prefix_probe` —
    fleet directory beats the per-replica baseline) and persists all rows
    via :func:`benchmarks.common.write_artifact`."""
    import threading

    from tnn_tpu.serving import (EngineSupervisor, FaultPlan,
                                 InferenceEngine, Router, ServingMetrics)

    roles = (None if variant == "mixed"
         else ["prefill", "decode", "decode", "decode"])
    print(f"{label}: {n_long} long ({long_len} tok) + {n_chat} chat prompts, "
          f"variant={variant}" + ("" if roles is None else f", roles={roles}"))
    rng = np.random.default_rng(seed)
    # chat prompts share four 8-token (two-block) "system prompt" prefixes;
    # long prompts are distinct — their win is the boundary handoff
    n_groups = 4
    prefixes = [rng.integers(0, model.vocab_size,
                             2 * block_size).astype(np.int32)
                for _ in range(n_groups)]
    longs = [rng.integers(0, model.vocab_size, long_len).astype(np.int32)
             for _ in range(n_long)]
    chats = [np.concatenate([prefixes[i % n_groups], rng.integers(
        0, model.vocab_size, block_size).astype(np.int32)])
        for i in range(n_chat)]
    # interleaved arrival order: one long, then two chats, repeating
    prompts, kinds = [], []
    li, ci = 0, 0
    while li < n_long or ci < n_chat:
        if li < n_long:
            prompts.append((longs[li], max_new_long))
            kinds.append("long")
            li += 1
        for _ in range(2):
            if ci < n_chat:
                prompts.append((chats[ci], max_new_chat))
                kinds.append("chat")
                ci += 1
    max_seq = long_len + max_new_long + block_size

    ref_engine = InferenceEngine(
        model, params, num_blocks=num_blocks, block_size=block_size,
        max_batch_size=max_batch_size, max_seq_len=max_seq, seed=seed)
    ref = []
    for p, mn in prompts:
        rid = ref_engine.submit(p, mn)
        ref.append(ref_engine.run_until_complete()[rid])

    wprompt = np.random.default_rng(seed + 1).integers(
        0, model.vocab_size, long_len).astype(np.int32)

    def mk_engine():
        return InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=max_seq,
            chunk_size=chunk_size, chunked_prefill=True, prefix_cache=True,
            decode_path="paged", seed=seed)

    engines = [mk_engine() for _ in range(4)]
    # Warm EVERY step shape the measured mix will execute, per replica.
    # On this eager CPU host each first-seen step signature — a prefill
    # chunk length, a decode batch row count, the adopt/export block
    # moves, the kv variant's resume-with-prefix-hit tail chunk —
    # compiles for multiple SECONDS, and a compile landing
    # mid-measurement pauses the engine loop and is charged as a
    # decode stall to whatever chat streams are co-resident. A steady-
    # state fleet never sees those one-time costs, so the A/B must not
    # either. The warmup prompts come from a different rng stream than
    # the workload, so no seeded block can serve a measured request.
    wrng = np.random.default_rng(seed + 1)
    wchats = [wrng.integers(0, model.vocab_size,
                            3 * block_size).astype(np.int32)
              for _ in range(3)]
    # wprompt + one token is exactly the post-handoff resume shape (a
    # full-chain prefix hit with a 1-token uncovered tail); the fresh
    # 41-token prompt is the recompute-resume shape (prompt + first
    # token re-prefilled from scratch)
    wresume = np.concatenate(
        [wprompt, wrng.integers(0, model.vocab_size, 1).astype(np.int32)])
    wrecompute = wrng.integers(0, model.vocab_size,
                               long_len + 1).astype(np.int32)
    wdonor = wrng.integers(0, model.vocab_size, long_len).astype(np.int32)
    # chats re-hitting a resident system prompt prefill only their tail
    # (a one-block pow2 bucket no full prompt ever compiles)
    whits = [np.concatenate(
        [wchats[0][:2 * block_size],
         wrng.integers(0, model.vocab_size, block_size).astype(np.int32)])
        for _ in range(2)]
    # a second 1-token-tail resume (distinct last token, same warmed
    # chain) plus a chat to hold in decode while it admits — see below
    wtail = np.concatenate(
        [wprompt, wrng.integers(0, model.vocab_size, 1).astype(np.int32)])
    wtail_chat = wrng.integers(0, model.vocab_size,
                               3 * block_size).astype(np.int32)
    for i, eng in enumerate(engines):
        wids = [eng.submit(wprompt, 2)]
        eng.run_until_complete()
        if i == 0:
            # the donor chain exists ONLY on engine 0, so the other
            # replicas' adopts below do real verified writes
            wids.append(eng.submit(wdonor, 2))
            eng.run_until_complete()
            wire = eng.export_prefix(wdonor)
        # concurrent mix: resume shapes + chats drive every decode
        # batch row count up to max_batch_size and every chunk-width
        # bucket, both solo and co-scheduled with decodes
        wids.append(eng.submit(wresume, 2))
        wids.append(eng.submit(wrecompute, 2))
        wids += [eng.submit(c, 2) for c in wchats]
        wids.append(eng.submit(whits[0], 2))
        eng.run_until_complete()
        wids.append(eng.submit(whits[1], 2))
        eng.run_until_complete()
        # a handed-off resume admits as a ONE-token chunk (its whole
        # prompt is a prefix hit) while chat decodes are already live —
        # a ('mixed', b, qw=1, nb) signature none of the packs above
        # trace, because wresume always co-admits with a wider chunk.
        # Park a chat in steady-state decode first, then admit the
        # 1-token tail against it.
        wids.append(eng.submit(wtail_chat, 6))
        for _ in range(3):
            eng.step()
        wids.append(eng.submit(wtail, 2))
        eng.run_until_complete()
        for w in wids:
            del eng.requests[w]
    for eng in engines[1:]:
        eng.adopt_prefix(wire)
        eng.export_prefix(wprompt)   # decode replicas export fleet pulls
    for eng in engines:
        eng.metrics = ServingMetrics(eng.profiler, slo_ttft_s=slo_ttft_s)
        # realistic cost model (applied AFTER warmup): decode steps cost
        # step_delay_s; prefill chunks additionally cost
        # prefill_delay_per_token_s per prompt token, so a monolithic
        # long chunk visibly stalls co-scheduled decodes the way a real
        # forward pass would
        eng.faults = FaultPlan()
        eng.faults.step_delay_s = float(step_delay_s)
        eng.faults.prefill_delay_per_token_s = \
            float(prefill_delay_per_token_s)
    sups = [EngineSupervisor(e, max_restarts=3, restart_backoff_s=0.0,
                             drain_deadline_s=60.0) for e in engines]
    # gray-failure mitigation (hedging/ejection) off for EVERY variant:
    # the A/B isolates the placement policy, and on an oversubscribed CPU
    # host the adaptive hedge threshold fires on ordinary queueing noise,
    # migrating streams mid-flight and swamping the stall/TTFT tails with
    # multi-second recompute gaps unrelated to disaggregation
    rkw = dict(hedge_budget=0.0, degrade_factor=0.0)
    if roles is not None:
        rkw.update(roles=roles, disagg_prompt_threshold=long_len // 2,
                   handoff_kv=(variant == "kv"),
                   fleet_prefix=(variant == "kv"))
    router = Router(sups, seed=seed, **rkw)

    lock = threading.Lock()
    terminals, done, times = {}, {}, {}

    def mk_listener():
        def listener(ev):
            with lock:
                if ev["event"] == "token":
                    times.setdefault(ev["id"], []).append(
                        time.perf_counter())
                    return
                terminals[ev["id"]] = terminals.get(ev["id"], 0) + 1
                if ev["event"] == "done":
                    done[ev["id"]] = ev
        return listener

    router.start()
    t0 = time.perf_counter()
    gids, owner = [], {}
    for i, (p, mn) in enumerate(prompts):
        time.sleep(gap_s)
        g = router.submit(p, mn, listener=mk_listener())
        gids.append(g)
        owner[g] = i
    deadline = time.monotonic() + 120.0
    while True:
        with lock:
            if sum(terminals.values()) >= len(gids):
                break
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"disagg bench wedged: {sum(terminals.values())}"
                f"/{len(gids)} terminal")
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    st = router.stats()
    router.request_drain("bench complete")
    if not router.join(timeout=60):
        raise RuntimeError("router failed to drain")

    # the disaggregation contract IS the gate
    assert all(terminals.get(g, 0) == 1 for g in gids), \
        "duplicated or missing terminal events"
    assert len(done) == len(gids), \
        f"only {len(done)}/{len(gids)} requests FINISHED"
    exact = int(all(done[g]["tokens"] == ref[owner[g]] for g in gids))
    assert exact, "a disaggregated stream diverged from the reference"
    for i, eng in enumerate(engines):
        assert eng.pool.num_allocated == 0, f"replica {i} leaked KV blocks"
        eng.check_invariants()
    if roles is not None:
        assert st["boundary_handoffs"] == n_long, \
            (f"expected every long prompt to cross the prefill->decode "
             f"boundary: {st['boundary_handoffs']} != {n_long}")
        if variant == "kv":
            assert st["handoff_fallbacks"] == 0, \
                "a fault-free KV handoff degraded to recompute-resume"
    adopted = sum(e.metrics.summary()["handoff_adopted_blocks"]
                  for e in engines)
    if variant == "kv":
        assert adopted > 0, "KV handoff never moved a block"

    chat_gids = [g for g in gids if kinds[owner[g]] == "chat"]
    chat_ttfts = np.array([done[g]["ttft_ms"] for g in chat_gids], float)
    ttfts = np.array([done[g]["ttft_ms"] for g in gids], float)
    stalls = []   # inter-token gaps of chat decode streams, ms
    for g in chat_gids:
        ts = times.get(g, [])
        stalls.extend(
            [(b - a) * 1e3 for a, b in zip(ts, ts[1:])])
    stalls = np.array(stalls or [0.0], float)
    row = report(
        label, wall, items=len(gids), item_name="req",
        extra={"requests": len(gids),
               "n_long": n_long,
               "n_chat": n_chat,
               "disagg": int(roles is not None),
               "kv_handoff": int(variant == "kv"),
               "fleet_prefix": int(variant == "kv"),
               "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3),
               "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3),
               "chat_ttft_ms_p99":
                   round(float(np.percentile(chat_ttfts, 99)), 3),
               "decode_stall_ms_p50":
                   round(float(np.percentile(stalls, 50)), 3),
               "decode_stall_ms_p99":
                   round(float(np.percentile(stalls, 99)), 3),
               "boundary_handoffs": st["boundary_handoffs"],
               "handoff_fallbacks": st["handoff_fallbacks"],
               "fleet_prefix_pulls": st["fleet_prefix_pulls"],
               "handoff_adopted_blocks": adopted,
               "exact_vs_ref": exact,
               "terminal": int(sum(terminals.values()))})
    if shared is not None:
        shared.setdefault("rows", []).append(row)
        if variant == "kv":
            mixed = [r for r in shared["rows"] if not r.get("disagg")]
            if mixed:
                assert (row["chat_ttft_ms_p99"]
                        < mixed[0]["chat_ttft_ms_p99"]), \
                    (f"disaggregation did not improve chat tail TTFT: "
                     f"{row['chat_ttft_ms_p99']} >= "
                     f"{mixed[0]['chat_ttft_ms_p99']}")
                assert (row["decode_stall_ms_p99"]
                        < mixed[0]["decode_stall_ms_p99"]), \
                    (f"disaggregation did not improve decode-stall p99: "
                     f"{row['decode_stall_ms_p99']} >= "
                     f"{mixed[0]['decode_stall_ms_p99']}")
                row["gate_chat_ttft_p99_improved"] = 1
                row["gate_decode_stall_p99_improved"] = 1
            if "handoff_probe" not in shared:
                shared["handoff_probe"] = _handoff_probe(
                    model, params, seed=seed)
            if "fleet_probe" not in shared:
                shared["fleet_probe"] = _fleet_prefix_probe(
                    model, params, seed=seed)
            row.update(shared["handoff_probe"])
            row.update(shared["fleet_probe"])
            if artifact:
                write_artifact(artifact, shared["rows"], label="disagg A/B")
                row["artifact_path"] = artifact
    return row


def bench_trace(model, params, *, num_requests: int = 6, prompt_len: int = 6,
                max_new: int = 8, replicas: int = 2, num_blocks: int = 16,
                block_size: int = 4, max_batch_size: int = 4,
                out_dir: str = "benchmarks/results",
                label: str = "serve_trace", seed: int = 0):
    """Observability gate shaped like a bench row: drive a traced 2-replica
    Router inline, drain, and persist the artifacts under ``out_dir`` —
    one merged Chrome/Perfetto trace (router + every replica on its own
    track), per-replica flight-recorder drain dumps, and a parsed
    Prometheus exposition. The row self-asserts that every artifact
    exists and parses, so a broken span/recorder/exposition pipeline
    fails CI the same way a perf regression would."""
    import json as json_lib
    import os

    from tnn_tpu.profiling.profiler import Profiler
    from tnn_tpu.serving import (EngineSupervisor, InferenceEngine, Router,
                                 render_prometheus)

    print(f"{label}: {num_requests} requests across {replicas} traced "
          f"replicas, artifacts under {out_dir}/")
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).astype(np.int32)
               for _ in range(num_requests)]

    profilers, sups = [], []
    for i in range(replicas):
        prof = Profiler(source=f"replica{i}")
        profilers.append(prof)
        eng = InferenceEngine(
            model, params, num_blocks=num_blocks, block_size=block_size,
            max_batch_size=max_batch_size, max_seq_len=prompt_len + max_new,
            seed=seed, profiler=prof, trace=True)
        sups.append(EngineSupervisor(
            eng, drain_deadline_s=60.0,
            flight_dir=os.path.join(out_dir, f"flight_r{i}")))
    router_prof = Profiler(source="router")
    router = Router(sups, seed=seed, profiler=router_prof)

    terminals = {}

    def mk_listener():
        def listener(ev):
            if ev["event"] != "token":
                terminals[ev["id"]] = ev
        return listener

    t0 = time.perf_counter()
    gids = [router.submit(p, max_new, listener=mk_listener())
            for p in prompts]
    router.run_sync(max_rounds=10_000)
    router.request_drain("bench complete")
    router.run_sync(max_rounds=10_000)
    wall = time.perf_counter() - t0

    assert len(terminals) == len(gids), \
        f"only {len(terminals)}/{len(gids)} requests terminal"
    assert all(ev["event"] == "done" for ev in terminals.values())
    assert all("trace_id" in ev and "latency_breakdown" in ev
               for ev in terminals.values()), \
        "terminal events lack observability fields"

    # artifact 1: merged Perfetto trace, one track per source
    trace_path = os.path.join(out_dir, "serve_trace.trace.json")
    for prof in profilers:
        router_prof.merge(prof)
    router_prof.to_chrome_trace(trace_path)
    with open(trace_path) as f:
        trace = json_lib.load(f)["traceEvents"]
    span_events = [e for e in trace if e.get("ph") == "X"]
    tracks = {e["args"]["name"] for e in trace if e.get("ph") == "M"}
    assert span_events, "merged trace has no span events"
    assert "router" in tracks and len(tracks) >= replicas + 1, \
        f"expected router + {replicas} replica tracks, got {tracks}"

    # artifact 2: per-replica flight-recorder drain dumps (JSONL)
    flight_records = 0
    for i, sup in enumerate(sups):
        assert sup.flight_dumps, f"replica {i} dumped no flight recordings"
        for path in sup.flight_dumps:
            with open(path) as f:
                lines = [json_lib.loads(ln) for ln in f if ln.strip()]
            assert lines[0]["kind"] == "flight_recorder_meta"
            flight_records += len(lines) - 1

    # artifact 3: Prometheus exposition with per-replica labels
    prom_path = os.path.join(out_dir, "serve_trace.metrics.prom")
    text = render_prometheus(router.prometheus_series())
    with open(prom_path, "w") as f:
        f.write(text)
    assert 'replica="router"' in text and 'replica="0"' in text, \
        "exposition lacks per-replica labels"

    return report(
        label, wall, items=num_requests, item_name="req",
        extra={"requests": num_requests,
               "replicas": replicas,
               "trace_events": len(span_events),
               "trace_tracks": len(tracks),
               "flight_dumps": sum(len(s.flight_dumps) for s in sups),
               "flight_records": flight_records,
               "prometheus_lines": len(text.splitlines()),
               "trace_path": trace_path,
               "metrics_path": prom_path})


def _smoke_model():
    """Tiny random GPT-2 (2L/32d/2h): engine mechanics without model weight."""
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fewer requests, shorter generations")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny random model (CI-fast, CPU-safe)")
    ap.add_argument("--chaos", action="store_true",
                    help="tiny model under a seeded FaultPlan: asserts the "
                         "fault-tolerance contract (terminal states, zero "
                         "leaked blocks) and reports it as a bench row")
    ap.add_argument("--avail", action="store_true",
                    help="tiny model through the replicated Router: baseline "
                         "vs one-replica-killed-mid-run A/B, asserting the "
                         "token-exact failover contract and reporting "
                         "goodput-at-SLO + p99 TTFT for both rows")
    ap.add_argument("--straggler", action="store_true",
                    help="tiny model through a 3-replica Router with one "
                         "persistently slow replica: mitigation-off vs "
                         "hedging+ejection-on A/B, asserting the token-"
                         "exact gray-failure contract and that the "
                         "mitigated row's p99 TTFT beats the unmitigated "
                         "twin's")
    ap.add_argument("--spike", action="store_true",
                    help="tiny model through a Router of host-tier-enabled "
                         "replicas under a trickle-then-burst arrival "
                         "trace: autoscaler-off vs autoscaler-on A/B, "
                         "asserting the on row's goodput-at-SLO strictly "
                         "beats the off twin's, zero-loss scale-down, "
                         "token-exact survivors, zero leaked blocks in "
                         "device pool and host tier, and a deterministic "
                         "host-tier hit-rate probe beating the no-tier "
                         "baseline")
    ap.add_argument("--disagg", action="store_true",
                    help="tiny model through a 3-replica Router: all-mixed "
                         "vs prefill/decode roles (recompute-resume) vs "
                         "roles + real KV-block handoff + fleet prefix "
                         "directory, asserting the kv row's chat TTFT p99 "
                         "and decode-stall p99 beat the mixed twin, "
                         "token-exact streams, zero leaked blocks, and the "
                         "deterministic handoff-cheaper / fleet-hit-rate "
                         "probes")
    ap.add_argument("--tp", action="store_true",
                    help="tiny model, tp=1 vs tp=2 tensor-parallel A/B on "
                         "the paged path: asserts the tp row's streams are "
                         "token-exact vs tp=1 and reports the per-chip "
                         "capacity headline (KV bytes per shard divided by "
                         "tp, max_concurrent_at_slo from a per-chip HBM "
                         "budget); needs >=2 JAX devices (CPU: "
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--longctx", action="store_true",
                    help="tiny model, sp=1 vs sp=2 (vs sp=4 given 4 "
                         "devices) sequence-parallel long-context A/B: "
                         "same per-chip KV footprint per row, asserting "
                         "max_context_blocks scales exactly ~N x, short "
                         "decode streams token-exact vs sp=1, and the "
                         "long-prompt row (KV > one chip's pool) serves "
                         "token-exact at sp>1 / fails cleanly at sp=1; "
                         "needs >=2 JAX devices (CPU: "
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--trace", action="store_true",
                    help="tiny model through a traced 2-replica Router: "
                         "persists the merged Perfetto trace, per-replica "
                         "flight-recorder dumps, and a Prometheus scrape "
                         "under benchmarks/results/, self-asserting that "
                         "each artifact exists and parses")
    ap.add_argument("--model", default="gpt2_small")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean request arrivals per second")
    args = ap.parse_args(argv)

    rr = RowRunner()
    if args.tp:
        # tensor-parallel A/B: the same up-front greedy batch at tp=1 vs
        # tp=2 — the tp row self-asserts token-exact streams; the headline
        # is per-chip KV residency (bytes/token/shard exactly halved) and
        # the max_concurrent_at_slo lift that buys under a fixed per-chip
        # HBM budget. Skips (no rows) on a genuinely single-device host.
        if jax.device_count() < 2:
            print("serve_bench --tp: needs >=2 JAX devices (set "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                  "before jax imports for a virtual CPU mesh); skipping")
            return rr.results
        model, params = _smoke_model()
        tshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "tp_ab_smoke.json")
        for deg in (1, 2):
            rr.add(lambda d=deg: bench_tp(
                model, params, num_requests=4, prompt_len=8, max_new=16,
                num_blocks=32, block_size=4, max_batch_size=4, tp=d,
                label=f"serve_tp{d}", shared=tshared, artifact=art),
                label=f"bench_tp_{deg}")
        return rr.results
    if args.longctx:
        # sequence-parallel long-context A/B: fixed per-chip pool, the
        # context mesh makes the AGGREGATE pool sp x deeper — the sp rows
        # self-assert token-exact short streams vs sp=1 and the headline
        # long-prompt gate (serves at sp>1, clean admission error at
        # sp=1). Skips (no rows) on a genuinely single-device host; the
        # sp=4 row needs 4 devices.
        if jax.device_count() < 2:
            print("serve_bench --longctx: needs >=2 JAX devices (set "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                  "before jax imports for a virtual CPU mesh); skipping")
            return rr.results
        model, params = _smoke_model()
        lshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "longctx_ab_smoke.json")
        degrees = (1, 2, 4) if jax.device_count() >= 4 else (1, 2)
        for deg in degrees:
            rr.add(lambda d=deg: bench_longctx(
                model, params, sp=d, sp_max=degrees[-1],
                label=f"serve_longctx_sp{d}", shared=lshared, artifact=art),
                label=f"bench_longctx_{deg}")
        return rr.results
    if args.disagg:
        # disaggregated-serving A/B: the same long+chat mix all-mixed, with
        # prefill/decode roles but recompute-resume handoff, and with real
        # KV-block handoff + the fleet prefix directory — the kv row gates
        # the tail-latency wins vs the mixed twin and both deterministic
        # probes (handoff cheaper than recompute; fleet cache beats the
        # per-replica baseline), then persists all three rows
        model, params = _smoke_model()
        dshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "disagg_ab_smoke.json")
        for var in ("mixed", "recompute", "kv"):
            rr.add(lambda v=var: bench_disagg(
                model, params, variant=v, shared=dshared, artifact=art,
                label=f"serve_disagg_{v}"),
                label=f"bench_disagg_{var}")
        return rr.results
    if args.spike:
        # elastic-fleet A/B: the same trickle-then-burst trace through
        # host-tier-enabled replicas, pinned at 1 replica vs under the
        # load-driven autoscaler — the on row asserts goodput strictly
        # improves, scale-down loses nothing, and the host tier's
        # deterministic hit-rate probe beats the (zero) no-tier baseline
        model, params = _smoke_model()
        spshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "spike_ab_smoke.json")
        for tag, on in (("off", False), ("on", True)):
            rr.add(lambda t=tag, a=on: bench_spike(
                model, params, num_requests=24, prompt_len=12, max_new=8,
                num_blocks=24, block_size=4, max_batch_size=4, autoscale=a,
                burst_rate_per_s=400.0,
                shared=spshared, artifact=art, label=f"serve_spike_{t}"),
                label=f"bench_spike_{tag}")
        return rr.results
    if args.trace:
        model, params = _smoke_model()
        rr.add(lambda: bench_trace(model, params), label="bench_trace")
        return rr.results
    if args.chaos:
        model, params = _smoke_model()
        rr.add(lambda: bench_chaos(model, params, num_requests=8, max_new=8,
                                   label="serve_chaos"),
               label="bench_chaos")
        return rr.results
    if args.straggler:
        # gray-failure A/B: the same Poisson trace through a 3-replica
        # Router with replica 0 persistently slow — pure JSQ (mitigation
        # off) keeps feeding the straggler; the mitigated row hedges late
        # first tokens, ejects the straggler as DEGRADED, and proactively
        # migrates its streams. The on-row asserts p99 TTFT strictly
        # beats the off-row and persists both as one artifact
        model, params = _smoke_model()
        sshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "straggler_ab_smoke.json")
        for tag, mit in (("off", False), ("on", True)):
            rr.add(lambda t=tag, m=mit: bench_straggler(
                model, params, replicas=3, num_requests=10,
                rate_per_s=100.0, prompt_len=6, max_new=6, num_blocks=16,
                block_size=4, max_batch_size=4, mitigate=m,
                shared=sshared, artifact=art,
                label=f"serve_straggler_{t}"),
                label=f"bench_straggler_{tag}")
        return rr.results
    if args.avail:
        # replicated-availability A/B: the same Poisson trace through a
        # 2-replica Router, untouched vs one replica hard-killed mid-run —
        # the goodput_at_slo / ttft_ms_p99 delta between the rows is the
        # measured cost of losing 1 of N replicas, and the killed row
        # self-asserts token-exact mid-stream migration
        model, params = _smoke_model()
        for tag, kill in (("baseline", False), ("killed", True)):
            rr.add(lambda t=tag, k=kill: bench_availability(
                model, params, replicas=2, num_requests=10,
                rate_per_s=100.0, prompt_len=6, max_new=8, num_blocks=16,
                block_size=4, max_batch_size=4, kill=k,
                label=f"serve_avail_{t}"),
                label=f"bench_availability_{tag}")
        return rr.results
    if args.smoke:
        # standard/paged A/B even in smoke: the decode_path column is the
        # benchmark's whole point after the paged rewire
        model, params = _smoke_model()
        for path in ("standard", "paged"):
            rr.add(lambda p=path: bench_serving(
                model, params, num_requests=6, rate_per_s=50.0, prompt_len=6,
                max_new=8, num_blocks=16, block_size=4, max_batch_size=4,
                label=f"serve_smoke_{p}", decode_path=p),
                label=f"bench_serving_{path}")
        # mixed-load chunked/whole A/B: 24-token prompts arrive while other
        # rows decode, so whole-prompt prefills stall the decode stream and
        # chunked prefill (chunk 8) interleaves it — compare ttft_ms_p99 and
        # decode_stall_ms_* between the two rows
        for tag, ckw in (("chunked", dict(chunked=True, chunk_size=8)),
                         ("whole", dict(chunked=False))):
            rr.add(lambda t=tag, c=dict(ckw): bench_serving(
                model, params, num_requests=6, rate_per_s=50.0,
                prompt_len=24, max_new=8, num_blocks=64, block_size=4,
                max_batch_size=4, label=f"serve_smoke_mixed_{t}", **c),
                label=f"bench_serving_mixed_{tag}")
        # shared-system-prompt A/B: a 48-token common prefix with 4-token
        # tails; the cached row forks the publisher's blocks and prefills
        # ~12x fewer tokens — compare prefill_tokens_saved and ttft_ms_p50
        # against the nocache twin
        for tag, cached in (("cached", True), ("nocache", False)):
            rr.add(lambda t=tag, c=cached: bench_prefix(
                model, params, num_requests=6, rate_per_s=50.0,
                prefix_len=48, tail_len=4, max_new=6, num_blocks=64,
                block_size=4, max_batch_size=4, cache=c,
                label=f"serve_smoke_prefix_{t}"),
                label=f"bench_prefix_{tag}")
        # speculative-decoding A/B: cyclic (repetitive) prompts, spec off vs
        # n-gram self-drafting vs tiny-draft-model scoring — the ngram row's
        # mean_accepted_per_step > 1 is the headline (gated in
        # tests/test_benchmarks.py); the draft row proves the plumbing (a
        # random-weight drafter buys ~0 acceptance but costs no exactness)
        for sp in ("off", "ngram", "draft"):
            rr.add(lambda s=sp: bench_spec(
                model, params, num_requests=6, prompt_len=16, max_new=12,
                num_blocks=64, block_size=4, max_batch_size=4, spec=s,
                spec_k=4, label=f"serve_smoke_spec_{s}"),
                label=f"bench_spec_{sp}")
        # sustained closed+open-loop load through the supervised runtime,
        # with one injected engine crash: goodput at the TTFT SLO, shed /
        # rejected / restart counters, and the zero-leak drain contract
        rr.add(lambda: bench_load(
            model, params, closed_users=3, closed_turns=3, open_requests=12,
            open_rate_per_s=60.0, prompt_len=6, max_new=6, num_blocks=16,
            block_size=4, max_batch_size=4, max_queue_depth=4, crash_step=9,
            label="serve_smoke_load"), label="bench_load")
        # engine-loop A/B: the same steady decode batch through the
        # synchronous vs overlapped loop — host_gap_ms_mean is the headline
        # (the overlapped row's speculatively adopted steps contribute zero
        # fetch->dispatch gap), with decode tok/s and token latency beside it
        for tag, ov in (("off", False), ("on", True)):
            rr.add(lambda t=tag, o=ov: bench_overlap(
                model, params, num_requests=4, prompt_len=8, max_new=24,
                num_blocks=32, block_size=4, max_batch_size=4, overlap=o,
                label=f"serve_smoke_overlap_{t}"),
                label=f"bench_overlap_{tag}")
        # quantized-serving A/B: f32 vs int8-KV vs int8-KV + int8 weights —
        # decode tok/s and TTFT beside the closeness columns (top-1/top-k
        # agreement, teacher-forced ppl_delta) and the capacity headline
        # (max_concurrent_at_slo from the pool's ACTUAL bytes/token); the
        # three rows persist as one JSON artifact under benchmarks/results/
        qshared = {}
        import os
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "quant_ab_smoke.json")
        for var in ("f32", "int8_kv", "int8_kv_w8"):
            rr.add(lambda v=var: bench_quant(
                model, params, num_requests=4, prompt_len=8, max_new=16,
                num_blocks=32, block_size=4, max_batch_size=4, variant=v,
                label=f"serve_smoke_quant_{v}", shared=qshared,
                artifact=art),
                label=f"bench_quant_{var}")
        return rr.results

    from tnn_tpu import models

    model = models.create(args.model)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    n, max_new = (8, 16) if args.quick else (32, 64)
    for path in ("standard", "paged"):
        rr.add(lambda p=path: bench_serving(
            model, params, num_requests=n, rate_per_s=args.rate,
            prompt_len=32, max_new=max_new, num_blocks=128, block_size=16,
            max_batch_size=8, label=f"serve_{args.model}_{p}",
            decode_path=p), label=f"bench_serving_{path}")
    # mixed-load chunked/whole A/B at the full prompt length (chunk 16 splits
    # each 32-token prompt into two mixed steps under decode load)
    for tag, ckw in (("chunked", dict(chunked=True, chunk_size=16)),
                     ("whole", dict(chunked=False))):
        rr.add(lambda t=tag, c=dict(ckw): bench_serving(
            model, params, num_requests=n, rate_per_s=args.rate,
            prompt_len=32, max_new=max_new, num_blocks=128, block_size=16,
            max_batch_size=8, label=f"serve_{args.model}_mixed_{t}", **c),
            label=f"bench_serving_mixed_{tag}")
    # shared-system-prompt A/B at model scale: 64-token common prefix (four
    # 16-token blocks) + 8-token tails, cache on vs off
    for tag, cached in (("cached", True), ("nocache", False)):
        rr.add(lambda t=tag, c=cached: bench_prefix(
            model, params, num_requests=n, rate_per_s=args.rate,
            prefix_len=64, tail_len=8, max_new=max_new, num_blocks=128,
            block_size=16, max_batch_size=8, cache=c,
            label=f"serve_{args.model}_prefix_{t}"),
            label=f"bench_prefix_{tag}")
    # speculative-decoding A/B at model scale: repetitive prompts, greedy;
    # compare tok/s and token_latency_ms_p50/p99 against acceptance rate
    for sp in ("off", "ngram"):
        rr.add(lambda s=sp: bench_spec(
            model, params, num_requests=n, prompt_len=32, max_new=max_new,
            num_blocks=128, block_size=16, max_batch_size=8, spec=s,
            spec_k=4, chunk_size=16, rate_per_s=args.rate,
            label=f"serve_{args.model}_spec_{s}"),
            label=f"bench_spec_{sp}")
    # supervised sustained-load row at model scale (one injected crash)
    rr.add(lambda: bench_load(
        model, params, closed_users=4, closed_turns=max(2, n // 8),
        open_requests=n, open_rate_per_s=args.rate * 2, prompt_len=32,
        max_new=max_new, num_blocks=128, block_size=16, max_batch_size=8,
        max_queue_depth=8, crash_step=12,
        label=f"serve_{args.model}_load"), label="bench_load")
    # engine-loop A/B at model scale: synchronous vs overlapped loop over a
    # steady decode batch — host_gap_ms_mean vs decode tok/s
    for tag, ov in (("off", False), ("on", True)):
        rr.add(lambda t=tag, o=ov: bench_overlap(
            model, params, num_requests=8, prompt_len=32, max_new=max_new,
            num_blocks=128, block_size=16, max_batch_size=8, overlap=o,
            label=f"serve_{args.model}_overlap_{t}"),
            label=f"bench_overlap_{tag}")
    # replicated-availability A/B at model scale: 3 replicas, one killed
    # mid-run in the second row (exactness is gated at smoke scale where a
    # serial reference is cheap; here the rows measure goodput under loss)
    for tag, kill in (("baseline", False), ("killed", True)):
        rr.add(lambda t=tag, k=kill: bench_availability(
            model, params, replicas=3, num_requests=n,
            rate_per_s=args.rate * 2, prompt_len=32, max_new=max_new,
            num_blocks=128, block_size=16, max_batch_size=8, kill=k,
            check_exact=False, label=f"serve_{args.model}_avail_{t}"),
            label=f"bench_availability_{tag}")
    # quantized-serving A/B at model scale: on a chip the int8 rows' decode
    # tok/s is the HBM-bandwidth headline; everywhere the closeness columns
    # (top-k agreement, ppl_delta) and max_concurrent_at_slo are the gate
    qshared = {}
    for var in ("f32", "int8_kv", "int8_kv_w8"):
        rr.add(lambda v=var: bench_quant(
            model, params, num_requests=n, prompt_len=32, max_new=max_new,
            num_blocks=128, block_size=16, max_batch_size=8, variant=v,
            label=f"serve_{args.model}_quant_{v}", shared=qshared),
            label=f"bench_quant_{var}")
    return rr.results


if __name__ == "__main__":
    import sys

    from benchmarks.common import ROW_FAILED

    rs = main()
    sys.exit(1 if any(str(r.get("bench", "")).startswith(ROW_FAILED)
                      for r in rs) else 0)
