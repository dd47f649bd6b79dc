"""``opcount/expert_gmm``'s count for a model whose every layer holds
experts (``decoder_sparse_step`` 1): ``num_hidden_layers`` expert layers,
experts of ``3 * hidden_size * moe_intermediate_size`` values,
``num_experts_per_tok`` picks a token; the shared expert is no part of the
grouped product and is not counted. The window's ``experts_hit_share`` and
``expert_held_share`` are means over all the layers."""
from chipbench.opcount import expert_gmm


def step_work(sz, hit_share, held_share, tokens):
    return expert_gmm.step_work(sz, hit_share, held_share, tokens)


def work_in_slice(obs, pattern=None):
    if "full_attention_interval" not in obs["sizes"]:
        return None                 # another family's run: nothing here
    return expert_gmm.work_in_slice(obs, pattern)
