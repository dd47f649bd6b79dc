"""What the grouped expert product has to move in a decode step: in every
layer, the three weights of each HELD expert that got a token (``3 * hidden *
moe_intermediate_size`` values, bf16), once, however few tokens it got; and
``6 * hidden * moe_intermediate_size`` operations for every assignment that
fell on a held expert. At about one token an expert that is one operation a
byte: bound by memory, by the experts HIT, not by the tokens.

The share of held experts with a token (``experts_hit_share``) and the share
of assignments that fell on held experts (``expert_held_share``) are the
window's own counters (``ServingMetrics.summary()``); the decode steps of the
traced slice are counted from the client's token events. UNDER-counted where
unsure: prompt chunks add nothing, a step's rows (in and out) are left out. A
program without the counters (the parent of the PR that added them) gives
nothing to read."""


def step_work(sz, hit_share, held_share, tokens, bytes_per_value=2):
    """{"flops", "bytes"} of ONE decode step of ``tokens`` rows."""
    expert = 3 * sz["hidden_size"] * sz["moe_intermediate_size"]
    layers = sz["num_hidden_layers"]
    return {"bytes": layers * hit_share * sz["held"] * expert
            * bytes_per_value,
            "flops": layers * held_share * tokens
            * sz["num_experts_per_tok"] * 2 * expert}


def work_in_slice(obs, pattern=None):
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    summary = obs.get("summary", {})
    if client is None or wall is None or "experts_hit_share" not in summary:
        return None
    t0, t1 = wall
    per_request = [sum(1 for i, t in enumerate(r.token_times)
                       if i and t0 <= t < t1) for r in client.reqs.values()]
    tokens = sum(per_request)
    if not tokens:
        return None
    # a row gets one token a decode step: the busiest row saw every step
    steps = max(per_request)
    work = step_work(obs["sizes"], summary["experts_hit_share"],
                     summary["expert_held_share"], tokens / steps)
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * steps * share for k, v in work.items()}
