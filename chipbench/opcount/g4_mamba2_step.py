"""What a decode step of a Mamba-2 layer's recurrence has to move: for every
decoded token, in every Mamba layer and every head, the head's state ``(P,
N)`` float32 read ONCE and written ONCE, beside its input ``x`` (P values),
its step and its decay; the rows ``B`` and ``C`` (N values each) once a layer
for all heads; at one row in ``SNAPSHOT_EVERY`` (the rows whose position is a
multiple of it) the state it read written once more, into a snapshot slot.
About 5 operations a state value (the decay, the outer product's multiply and
add, the read-out's multiply and add) against 8 bytes: bound by memory.

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure: prompt chunks (the chunked
form, no kernel) add nothing, the convolution's positions and the kernel's
output are left out."""

SNAPSHOT_EVERY = 16     # tnn_tpu.serving.kv_pool.SNAPSHOT_EVERY


def token_work(sz):
    """{"flops", "bytes"} of ONE decoded token."""
    layers = sz["layer_types"].count("mamba")
    heads, p, n = sz["mamba_n_heads"], sz["mamba_d_head"], sz["mamba_d_state"]
    state = p * n * 4
    head = 2 * state + state / SNAPSHOT_EVERY + (p + 2) * 4
    return {"bytes": layers * (heads * head + 2 * n * 4),
            "flops": layers * heads * 5 * p * n}


def work_in_slice(obs, pattern=None):
    """The decode work of the traced slice: the rate over the host's
    interval [start_trace, stop_trace], times the length the device trace
    really covers (the profiler starts late and stops early)."""
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None \
            or "mamba_n_heads" not in obs["sizes"]:
        return None
    t0, t1 = wall
    tokens = sum(1 for r in client.reqs.values()
                 for i, t in enumerate(r.token_times) if i and t0 <= t < t1)
    if not tokens:
        return None
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * tokens * share for k, v in token_work(obs["sizes"]).items()}
