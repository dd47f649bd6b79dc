"""What a decode step of the gated delta rule has to move: for every decoded
token, in every linear layer and every value head, the head's state ``(Dk,
Dv)`` float32 read ONCE and written ONCE, beside its query, key, value, decay
and beta; at one row in ``SNAPSHOT_EVERY`` (the rows whose position is a
multiple of it) the state it read written once more, into a snapshot slot.
About 7 operations a state value (decay, the read-out of ``k``, the update,
the read-out of ``q``) against 8 bytes: bound by memory.

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure: prompt chunks (the chunked
form, no kernel) add nothing, the convolution's positions are left out."""

SNAPSHOT_EVERY = 16     # tnn_tpu.serving.kv_pool.SNAPSHOT_EVERY


def token_work(sz):
    """{"flops", "bytes"} of ONE decoded token."""
    layers = sz["layer_types"].count("linear_attention")
    heads = sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    state = dk * dv * 4
    head = 2 * state + state / SNAPSHOT_EVERY + (2 * dk + dv + 2) * 4
    return {"bytes": layers * heads * head,
            "flops": layers * heads * 7 * dk * dv}


def work_in_slice(obs, pattern=None):
    """The decode work of the traced slice: the rate over the host's
    interval [start_trace, stop_trace], times the length the device trace
    really covers (the profiler starts late and stops early)."""
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None \
            or "linear_num_value_heads" not in obs["sizes"]:
        return None
    t0, t1 = wall
    tokens = sum(1 for r in client.reqs.values()
                 for i, t in enumerate(r.token_times) if i and t0 <= t < t1)
    if not tokens:
        return None
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * tokens * share for k, v in token_work(obs["sizes"]).items()}
