"""What decides ``correct`` for a run of the ``serve_stdin`` driver.

(a) What a client and an operator can see: how every measured request ended,
the window's own counters, the path the engine took, and that nothing
compiled inside the window.
(b) The served tokens themselves against the plain reference. Once the window
has closed and the engine is gone, a sample of the window's requests, drawn
from the seed and always holding the longest, is run through the reference
(float32, precision "highest") teacher-forced over prompt + streamed tokens;
each streamed token's reference logit is held against the reference's best
logit at its position. Greedy tokens of a random-weight model sit on near
ties, so the two numbers compared are gaps in logits, not token ids:

  gap_max    the widest gap of any sampled token
  gap_mean   the mean gap over all sampled tokens (steady from seed to seed;
             grows with the square of the arithmetic's error)

Each has its limit in the configuration file (``limits``), set from chip
readings of sound runs and of the fp8 control (``chipbench.control``).
"""
from __future__ import annotations

import numpy as np


def sample_requests(obs, k):
    """k requests that streamed tokens, drawn from the seed, the longest
    always among them."""
    ctx = obs["ctx"]
    reqs = sorted((r for r in obs["client"].reqs.values()
                   if r.measured and r.streamed), key=lambda r: r.id)
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: len(r.tokens) + len(r.streamed))
    rest = [r for r in reqs if r is not longest]
    rng = np.random.default_rng([ctx.seed % (2 ** 63), 3])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in pick]


def gap_readings(obs, sample, control=None):
    """(gap_max, gap_mean, tokens compared). With ``control`` (a precision
    below the configuration's), the tokens judged are not the served ones but
    those the reference computed in THAT precision puts first."""
    reference, sz = obs["reference"], obs["sizes"]
    # built for the longest sampled request, rounded up as the reference
    # asks, not for the most positions the configuration declares
    length = reference.forward_length(sz, max(
        (len(r.tokens) + len(r.streamed) for r in sample), default=1))
    fwd = reference.Forward(obs["params"], sz, length)
    low = reference.Forward(obs["params"], sz, length, quant=control) \
        if control else None
    gaps = []
    for r in sample:
        ids = list(r.tokens) + list(r.streamed)
        pos = np.arange(len(r.tokens) - 1, len(ids) - 1)
        lg = fwd.rows(ids, pos)
        toks = np.asarray(r.streamed) if low is None \
            else low.rows(ids, pos).argmax(axis=-1)
        gaps.append(lg.max(axis=-1) - lg[np.arange(len(pos)), toks])
    if not gaps:
        return float("inf"), float("inf"), 0
    allg = np.concatenate(gaps)
    if not np.isfinite(allg).all():
        return float("inf"), float("inf"), len(allg)
    return float(allg.max()), float(allg.mean()), len(allg)


def judge(obs):
    """(correct, attempted, failed), every number printed beside its limit."""
    ctx, client, traffic = obs["ctx"], obs["client"], obs["traffic"]
    config, summary, eng = ctx.config, obs["summary"], obs["engine"]
    marks = obs["marks"]
    hold = ctx.hold

    measured = [r for r in client.reqs.values() if r.measured]
    errored = [r for r in measured if r.end not in ("done", "cancelled", None)]
    # an open loop hangs up a stated grace after its window: a measured
    # request not done by then has failed (a closed loop's are cut off there
    # by design)
    late = [r for r in measured if r.end != "done"] \
        if "grace_s" in traffic else []
    failed = len({r.id for r in errored + late})
    vocab = obs["sizes"]["vocab_size"]
    hold("server_rc", obs["rc"], 0, obs["rc"] == 0, "server exit code")
    hold("errored", len(errored), 0, not errored, "requests ended in error")
    hold("stray_errors", len(client.stray_errors), 0,
         not client.stray_errors, "error events of no request")
    for key in ("engine_restarts", "step_retries", "failed"):
        hold(f"summary_{key}", summary.get(key, "missing"), 0,
             summary.get(key, "missing") == 0, "the window's own counter")
    hold("decode_path", f"{eng['decode_path']!r} fallback "
         f"{eng['paged_fallback_reason']!r}", "'paged' None",
         eng["decode_path"] == "paged"
         and eng["paged_fallback_reason"] is None)
    if not ctx.rehearse:
        from tnn_tpu.ops.pallas.runtime import interpret_default

        hold("kernels_interpreted", interpret_default(), False,
             not interpret_default())
        hold("pool_on", eng["pool_platforms"], ["tpu"],
             eng["pool_platforms"] == ["tpu"], "where the pool's pages are")
    new_keys = marks["keys_close"] - marks["keys_open"]
    hold("compiled_in_window", sorted(map(str, new_keys)), [], not new_keys,
         "programs compiled inside the window")
    wrong = [r.id for r in measured
             if len(r.streamed) > r.max_new
             or (r.end == "done" and len(r.streamed) != r.max_new)
             or any(not 0 <= t < vocab for t in r.streamed)]
    hold("wrong_streams", wrong, [], not wrong, "requests with a wrong "
         "count of tokens or one outside the vocabulary")
    n_tok = sum(len(r.streamed) for r in measured)
    hold("tokens_streamed", n_tok, ">= 1", n_tok >= 1,
         "by measured requests")

    sample = sample_requests(obs, config["check"]["sample_requests"])
    gmax, gmean, n = gap_readings(obs, sample)
    limits = (config["rehearsal"] if ctx.rehearse else config)["limits"]
    ctx.note(f"reference sample: {len(sample)} requests, {n} served tokens, "
             f"longest {max((len(r.tokens) + len(r.streamed) for r in sample), default=0)}")
    hold("gap_max", gmax, limits["gap_max"], gmax <= limits["gap_max"],
         "widest gap of a served token's reference logit below the "
         "reference's best")
    hold("gap_mean", gmean, limits["gap_mean"], gmean <= limits["gap_mean"],
         "mean of those gaps")
    obs["readings"] = {"gap_max": gmax, "gap_mean": gmean, "tokens": n}
    obs["facts"] = facts(obs)
    correct = all(c["ok"] for c in ctx.checks.values())
    return correct, len(measured), failed


def facts(obs):
    client = obs["client"]
    return {"prompt_tokens_admitted": sum(
        len(r.tokens) for r in client.reqs.values()
        if r.sent is not None and client.t_open <= r.sent < client.t_close)}


def also_worth_reading(obs):
    """Lines for a reader of the run, not for the driver: medians, p99s,
    how late the generator ran, how the requests ended."""
    from chipbench import stats
    from chipbench.end_to_end import itl_p95_ms, out_tok_s, ttft_p90_ms

    client = obs["client"]
    measured = [r for r in client.reqs.values() if r.measured]
    ttft, itl = ttft_p90_ms.samples(obs), itl_p95_ms.samples(obs)
    ends = {}
    for r in measured:
        ends[r.end] = ends.get(r.end, 0) + 1
    late = [1e3 * x for x in client.lateness]
    s = obs["summary"]
    yield (f"requests measured {len(measured)}, ended {ends}; tokens in "
           f"window {sum(1 for r in client.reqs.values() for t in r.token_times if client.t_open <= t < client.t_close)}")
    yield (f"ttft ms: n {len(ttft)} mean {sum(ttft) / max(1, len(ttft))} "
           f"sorted {sorted(round(t) for t in ttft)}")
    yield (f"ttft ms: n {len(ttft)} p50 {stats.percentile(ttft, 50)} p90 "
           f"{stats.percentile(ttft, 90)} p99 {stats.percentile(ttft, 99)} "
           f"max {max(ttft, default=None)}")
    yield (f"gap ms: n {len(itl)} p50 {stats.percentile(itl, 50)} p95 "
           f"{stats.percentile(itl, 95)} p99 {stats.percentile(itl, 99)}")
    half = (client.t_open + client.t_close) / 2
    first = [1e3 * r.ttft for r in measured if r.ttft and r.due < half]
    second = [1e3 * r.ttft for r in measured if r.ttft and r.due >= half]
    yield (f"ttft ms p50 of requests due in the first half {stats.percentile(first, 50)} "
           f"(n {len(first)}), in the second {stats.percentile(second, 50)} "
           f"(n {len(second)}); without a first token at hang-up "
           f"{sum(1 for r in measured if not r.token_times)}; not ended at "
           f"window close {sum(1 for r in client.reqs.values() if r.sent and (r.end_time is None or r.end_time > client.t_close) and r.sent < client.t_close)}")
    ts = sorted(t for r in client.reqs.values() for t in r.token_times
                if client.t_open <= t < client.t_close)
    flushes = [a for a, b in zip(ts, ts[1:] + [float("inf")])
               if b - a >= out_tok_s.FLUSH_S]
    between = sorted(zip(stats.gaps(flushes), flushes), reverse=True)
    yield (f"flushes of tokens in the window {len(flushes)}; ms between "
           f"them p50 {stats.percentile([1e3 * g for g, _ in between], 50)}; "
           "the three longest (ms, s into the window) "
           f"{[(round(1e3 * g), round(t - client.t_open, 1)) for g, t in between[:3]]}")
    yield (f"generator lateness ms: p50 {stats.percentile(late, 50)} max "
           f"{max(late, default=None)}")
    yield ("window summary: " + ", ".join(
        f"{k} {s.get(k)}" for k in (
            "steps", "decode_tokens", "prefill_tokens",
            "prefill_tokens_saved", "preemptions", "queue_wait_ms_p50",
            "queue_depth_max", "step_latency_ms_p50", "step_latency_ms_p99",
            "host_gap_ms_p50", "host_gap_ms_p99",
            "batch_fill_mean", "pool_occupancy_max")))
