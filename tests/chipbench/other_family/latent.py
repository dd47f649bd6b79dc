"""A stand-in reference module of ANOTHER family, for the harness's own test
(``test_chipbench_family.py``): it reads the key names that latent-attention
decoders publish (``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``max_position_embeddings``, ``kv_lora_rank``),
carries a size GPT-2 has not through ``sz``, and maps onto the tiny GPT-2
program, which is the only program a CPU test can serve. It is NOT a model:
its arithmetic is ``chipbench/reference/gpt2.py``'s. What it shows is the
contract a reference module keeps (``chipbench/README.md``) and that the
harness asks for nothing else.
"""
from chipbench.reference import gpt2

WIDTH_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
              "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
              "moe_intermediate_size", "num_experts_per_tok", "index_topk")
LENGTH_STEP = 32


def sizes_of(cfg):
    return dict(layers=int(cfg["num_hidden_layers"]),
                hidden=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                table=int(cfg["max_position_embeddings"]),
                kv_lora_rank=int(cfg["kv_lora_rank"]),    # GPT-2 has none
                vocab_size=int(cfg["vocab_size"]),
                positions=int(cfg["served_positions"]))


def _gpt2(sz):
    return dict(n_layer=sz["layers"], n_embd=sz["hidden"], n_head=sz["heads"],
                vocab_size=sz["vocab_size"], n_positions=sz["table"])


def make_params(sz, seed):
    return gpt2.make_params(_gpt2(sz), seed)


def check_program(model, sz, name):
    got = (model.num_layers, model.d_model, model.num_heads,
           model.vocab_size, model.max_len)
    want = (sz["layers"], sz["hidden"], sz["heads"], sz["vocab_size"],
            sz["table"])
    if got != want:
        raise SystemExit(f"the program's {name} has sizes {got}, the "
                         f"configuration file says {want}")


def forward_length(sz, longest):
    return min(sz["table"], -(-longest // LENGTH_STEP) * LENGTH_STEP)


def Forward(params, sz, length, quant=None):
    return gpt2.Forward(params, _gpt2(sz), length, quant)
