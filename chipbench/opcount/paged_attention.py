"""What paged decode attention has to move: for every decoded token, the keys
and values of its whole context, once in every layer. It is bound by memory
bandwidth (4 FLOPs per K/V value read, against the chip's ~240 FLOPs a byte).

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure: prompt chunks (which also run
the kernel) add no bytes here, queries and outputs are left out, and a page is
counted at its tokens, not at its padded size."""


def kv_bytes_per_context_token(sz, bytes_per_value=2):
    """K and V of one position, all layers."""
    return 2 * sz["n_layer"] * sz["n_embd"] * bytes_per_value


def decode_work(context_lengths, sz):
    """{"flops", "bytes"} of decode steps that attend over these contexts."""
    ctx = sum(context_lengths)
    return {"bytes": ctx * kv_bytes_per_context_token(sz),
            "flops": 4 * ctx * sz["n_embd"] * sz["n_layer"]}


def work_in_slice(obs, pattern=None):
    """The decode work of the traced slice: the rate over the host's
    interval [start_trace, stop_trace], times the length the device trace
    really covers (the profiler starts late and stops early)."""
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None:
        return None
    t0, t1 = wall
    # token i >= 1 of a request comes from a decode step whose row attends
    # over the prompt and the i tokens before it
    contexts = [len(r.tokens) + i for r in client.reqs.values()
                for i, t in enumerate(r.token_times) if i and t0 <= t < t1]
    if not contexts:
        return None
    work = decode_work(contexts, obs["sizes"])
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * share for k, v in work.items()}
