"""``opcount/windowed_paged_attention``'s count of one KIND of layer for a
model whose layers of kind ``attention`` keep pages and whose others keep a
state: for every decoded token, the keys and values of its whole context,
once in each ``attention`` layer, K and V of ``num_key_value_heads *
head_dim`` values each, bf16. At 32 heads over 8 that is 4 operations a
byte: bound by memory. Under-counted as that module is."""
from chipbench.opcount import windowed_paged_attention


def work_in_slice(obs, pattern=None):
    client, ctx = obs.get("client"), obs["ctx"]
    wall = getattr(ctx, "trace_wall", None)
    if client is None or wall is None \
            or "mamba_n_heads" not in obs["sizes"]:
        return None                 # another family's run: nothing here
    t0, t1 = wall
    contexts = [len(r.tokens) + i for r in client.reqs.values()
                for i, t in enumerate(r.token_times) if i and t0 <= t < t1]
    if not contexts:
        return None
    work = windowed_paged_attention.decode_work(contexts, obs["sizes"],
                                                "attention")
    share = obs["trace"]["window_s"] / (t1 - t0)
    return {k: v * share for k, v in work.items()}
