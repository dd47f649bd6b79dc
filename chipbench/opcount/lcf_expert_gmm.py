"""``opcount/expert_gmm``'s count for a model of shortcut blocks, by its own
key names: ONE expert layer a block (the configuration's ``num_layers``),
experts of ``expert_ffn_hidden_size``, ``moe_topk`` picks a token. The
window's ``expert_held_share`` is held assignments over ALL ``moe_topk``
picks (those on zero-compute experts too: they are picks, and take no
weight), ``experts_hit_share`` a mean over the expert layers."""
from chipbench.opcount import expert_gmm


def as_expert_gmm(sz):
    return dict(sz, num_hidden_layers=sz["num_layers"],
                moe_intermediate_size=sz["expert_ffn_hidden_size"],
                num_experts_per_tok=sz["moe_topk"])


def step_work(sz, hit_share, held_share, tokens):
    return expert_gmm.step_work(as_expert_gmm(sz), hit_share, held_share,
                                tokens)


def work_in_slice(obs, pattern=None):
    sz = obs["sizes"]
    if "moe_topk" not in sz:        # another family's run: nothing here
        return None
    return expert_gmm.work_in_slice(dict(obs, sizes=as_expert_gmm(sz)),
                                    pattern)
