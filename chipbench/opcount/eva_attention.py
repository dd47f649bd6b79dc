"""What EVA decode attention has to move: for every decoded token and layer,
the exact rows of its window (K and V of every position from the window's
first to the token's own) and every summary row it may read (one for each
chunk of each EARLIER window), bf16. It is bound by memory bandwidth (4 FLOPs
per K/V value read, against the chip's ~240 FLOPs a byte).

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure, as ``opcount/paged_attention``
is: prompt chunks (which also run the kernel) add nothing, queries, outputs,
``phi`` and ``mu`` are left out, and a page counts at its live rows, not at
its padded size."""


def rows_read(position, sz):
    """K/V rows a query at ``position`` attends over: (exact, summaries)."""
    w, c = sz["window_size"], sz["chunk_size"]
    return position % w + 1, (position // w) * (w // c)


def decode_work(positions, sz, bytes_per_value=2):
    """{"flops", "bytes"} of decode steps whose rows sit at ``positions``."""
    rows = sum(sum(rows_read(p, sz)) for p in positions)
    values = 2 * rows * sz["hidden_size"] * sz["num_hidden_layers"]
    return {"bytes": values * bytes_per_value, "flops": 4 * values}


def work_in_slice(obs, pattern=None):
    """The decode work of the traced slice: the rate over the host's
    interval [start_trace, stop_trace], times the length the device trace
    really covers (the profiler starts late and stops early)."""
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None:
        return None
    t0, t1 = wall
    # token i >= 1 of a request comes from a decode step whose row holds
    # the prompt and the i - 1 tokens before it, and writes position
    # len(prompt) + i - 1
    positions = [len(r.tokens) + i - 1 for r in client.reqs.values()
                 for i, t in enumerate(r.token_times) if i and t0 <= t < t1]
    if not positions:
        return None
    work = decode_work(positions, obs["sizes"])
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * share for k, v in work.items()}
