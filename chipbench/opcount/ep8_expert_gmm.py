"""``opcount/expert_gmm``'s count for a model whose first layers are dense:
the grouped expert product runs in the ``num_hidden_layers -
num_dense_layers`` layers that HOLD experts, and the window's counters
(``experts_hit_share``, ``expert_held_share``) are means over those layers.
Counting ``num_hidden_layers`` would put a dense layer's share on top: 5/4 too
high at one dense layer of five."""
from chipbench.opcount import expert_gmm


def expert_layers(sz):
    return sz["num_hidden_layers"] - sz.get("num_dense_layers", 0)


def step_work(sz, hit_share, held_share, tokens):
    return expert_gmm.step_work(
        dict(sz, num_hidden_layers=expert_layers(sz)), hit_share, held_share,
        tokens)


def work_in_slice(obs, pattern=None):
    sz = obs["sizes"]
    if "num_hidden_layers" not in sz:   # another family's run: nothing here
        return None
    return expert_gmm.work_in_slice(
        dict(obs, sizes=dict(sz, num_hidden_layers=expert_layers(sz))),
        pattern)
