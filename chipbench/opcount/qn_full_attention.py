"""``opcount/windowed_paged_attention``'s count of the GLOBAL layers for a
model whose other layers keep a state and no pages: for every decoded token,
the keys and values of its whole context, once in each of the
``full_attention`` layers (every ``full_attention_interval``-th), K and V of
``num_key_value_heads * head_dim`` values each, bf16. At 16 heads over 2 that
is 8 operations a byte: bound by memory. Under-counted as that module is."""
from chipbench.opcount import windowed_paged_attention


def work_in_slice(obs, pattern=None):
    if "full_attention_interval" not in obs["sizes"]:
        return None                 # another family's run: nothing here
    return windowed_paged_attention.work_in_slice(obs, pattern)
