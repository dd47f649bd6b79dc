"""``opcount/mla_attention``'s count for a model whose block holds TWO latent
attentions, each over cache rows of its own: a decoded token reads its
context's latent rows once in each of ``2 * num_layers`` cache layers (the
configuration's ``num_layers`` counts blocks). The same row arithmetic: a row
counts at its live ``kv_lora_rank + qk_rope_head_dim`` values (576 of the 640
lanes it takes in the pool), 64 heads multiply it for the score and its first
``kv_lora_rank`` values for the output: ~120 operations a byte, under the
chip's ~240: bound by memory."""
from chipbench.opcount import mla_attention

ATTENTIONS_A_BLOCK = 2


def cache_layers(sz):
    return ATTENTIONS_A_BLOCK * sz["num_layers"]


def decode_work(context_lengths, sz):
    return mla_attention.decode_work(
        context_lengths, dict(sz, num_hidden_layers=cache_layers(sz)))


def work_in_slice(obs, pattern=None):
    sz = obs["sizes"]
    if "num_layers" not in sz:      # another family's run: nothing here
        return None
    return mla_attention.work_in_slice(
        dict(obs, sizes=dict(sz, num_hidden_layers=cache_layers(sz))),
        pattern)
