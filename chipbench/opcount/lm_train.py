"""Model FLOPs of training a decoder LM, per token: 6 per parameter that
takes part in a matmul (forward 2, backward 4; the tied table counts once, as
the head; the position table does no matmul), plus attention's score and
value matmuls over the CAUSAL half of the context: 6 x n_layer x seq x n_embd
(the non-causal count, 12 x ..., would credit work a causal kernel need not
do). Recomputation is not counted."""
from chipbench.reference import gpt2


def matmul_params(sz):
    import math

    total = 0
    def walk(t, path=""):
        nonlocal total
        for k, v in t.items():
            if isinstance(v, dict):
                walk(v, path + "/" + k)
            elif len(v) == 2 and not path.endswith("/wpe"):
                total += math.prod(v)
    walk(gpt2.param_shapes(sz))
    return total


def flops_per_token(sz, seq):
    return 6 * matmul_params(sz) + 6 * sz["n_layer"] * seq * sz["n_embd"]
