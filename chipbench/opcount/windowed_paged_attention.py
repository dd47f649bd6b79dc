"""What paged decode attention has to move in a model of sliding-window
layers beside global layers, a KIND of layer at a time: for every decoded
token, the keys and values its query attends, once in every layer of the
kind: the last ``min(context, sliding_window)`` positions in a window layer,
the whole context in a global one. K and V of ``num_key_value_heads *
head_dim`` values each, bf16; every query head multiplies each for its score
and its output: ``4 * num_attention_heads * head_dim`` operations a position.
At 48 heads over 8 that is 6 operations a byte: bound by memory.

The two kinds run two kernels, told apart by the instruction's NAME: the
window layers' is ``tnn_paged_attention_win``, and a ``pattern`` that names
``_win`` asks for their work; any other for the global layers'.

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure, as ``opcount/paged_attention``
is: prompt chunks (which also run the kernels) add nothing, queries and
outputs are left out, and a window layer's straddled page counts at the
positions attended, not at the page fetched."""


def decode_work(context_lengths, sz, kind, bytes_per_value=2):
    """{"flops", "bytes"} of decode steps that attend over these contexts in
    the layers of ``kind`` ("sliding_attention" or "full_attention")."""
    layers = sz["layer_types"].count(kind)
    if kind == "sliding_attention":
        context_lengths = [min(c, sz["sliding_window"])
                           for c in context_lengths]
    ctx = sum(context_lengths) * layers
    return {"bytes": ctx * 2 * sz["num_key_value_heads"] * sz["head_dim"]
            * bytes_per_value,
            "flops": ctx * 4 * sz["num_attention_heads"] * sz["head_dim"]}


def work_in_slice(obs, pattern=None):
    """The decode work of the traced slice: the rate over the host's
    interval [start_trace, stop_trace], times the length the device trace
    really covers (the profiler starts late and stops early)."""
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None or "layer_types" not in obs["sizes"]:
        return None
    t0, t1 = wall
    # token i >= 1 of a request comes from a decode step whose row attends
    # over the prompt and the i tokens before it
    contexts = [len(r.tokens) + i for r in client.reqs.values()
                for i, t in enumerate(r.token_times) if i and t0 <= t < t1]
    if not contexts:
        return None
    kind = "sliding_attention" if "_win" in (pattern or "") \
        else "full_attention"
    work = decode_work(contexts, obs["sizes"], kind)
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * share for k, v in work.items()}
