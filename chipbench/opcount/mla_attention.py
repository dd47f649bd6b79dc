"""What latent (MLA) decode attention has to move: for every decoded token
and layer, ONE read of each live latent row of its context, ``[c_kv |
k_rope]`` (``kv_lora_rank + qk_rope_head_dim`` values, bf16): a row is key and
value at once. In the absorbed form every head multiplies the whole row for
its score and the row's first ``kv_lora_rank`` values for its output: ``2 *
heads * (latent + kv_lora_rank)`` operations a context position a row. At 32
heads that is ~58 operations a byte, under the chip's ~240: bound by memory.

Counted from the client's token events in the traced slice and the shapes in
the configuration; UNDER-counted where unsure, as ``opcount/paged_attention``
is: prompt chunks (which also run the kernel) add nothing, queries and
outputs are left out, and a row counts at its live values, not at the whole
lanes it is padded to in the pool."""


def decode_work(context_lengths, sz, bytes_per_value=2):
    """{"flops", "bytes"} of decode steps that attend over these contexts."""
    latent = sz["kv_lora_rank"] + sz["qk_rope_head_dim"]
    ctx = sum(context_lengths) * sz["num_hidden_layers"]
    return {"bytes": ctx * latent * bytes_per_value,
            "flops": ctx * 2 * sz["num_attention_heads"]
            * (latent + sz["kv_lora_rank"])}


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
