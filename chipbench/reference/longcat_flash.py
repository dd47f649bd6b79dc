"""LongCat-Flash-Omni's language model (https://huggingface.co/meituan-longcat/
LongCat-Flash-Omni, config.json: blocks of TWO latent-attention sublayers and
two dense feed-forwards with ONE shortcut expert layer across them, a softmax
router over 512 experts + 256 zero-compute identity experts, 12 a token)
written out in plain ``jax.numpy``: float32, matmul precision "highest", the
EXPANDED form of the attention (every head's keys and values made from the
latent row: no absorption), no cache, no kernels, no pages. It imports
nothing of the program. The audio and vision towers and the codec decoder
are not in the catalog's ``config`` and are not served.

There is no network in this sandbox: every equation below is in the catalog
entry's ``config`` and ``described_as``
(``/opt/skills/guides/model-configs/architectures.jsonl``) or is recalled from
the family's public model code (``modeling_longcat_flash``) and listed under
``assumed`` in the configuration file with its alternative. A builder who
knows the source to differ corrects THIS file first; the program follows it.

``x`` is the float32 residual, ``W`` bias-free, ``n(.)`` RMSNorm with a plain
gain and ``eps`` = ``rms_norm_eps``. One block (``num_layers`` counts blocks):

    a0 = x  + MLA_0(n(x))             h0 = n(a0)
    s  = MoE(h0)                      the shortcut: computed here, added last
    b0 = a0 + FFN_0(h0)               dense, ffn_hidden_size wide
    a1 = b0 + MLA_1(n(b0))
    y  = a1 + FFN_1(n(a1)) + s        four norms a block

    FFN(h) = W_down(silu(W_gate h) * (W_up h))
    MoE(h): p = softmax(h W_r)  float32, over ALL n_routed_experts + zero_expert_num
            E = the moe_topk largest of p + e_score_correction_bias
            w_e = routed_scaling_factor * p_e      (the bias only SELECTS; NOT renormalised)
            MoE = sum_{e in E, e < n_routed_experts} w_e FFN_e(h)
                  + (sum_{e in E, e >= n_routed_experts} w_e) * h   (identity experts)
    MLA(x): c_q = n(x W_qa);  [q_nope | q_rope] a head = c_q W_qb, both times
                (hidden / q_lora_rank)^1/2                  (mla_scale_q_lora)
            [c_kv | k_r] = x W_kva;  c_kv = n(c_kv) * (hidden / kv_lora_rank)^1/2
                                                            (mla_scale_kv_lora)
            [k_nope | v] a head = c_kv W_kvb;  k_rope = R_t k_r (ONE for all
            heads);  q_rope = R_t q_rope
            s = (q_nope . k_nope + q_rope . k_rope) * (qk_nope + qk_rope)^-1/2
            out = concat_heads(causal_softmax(s) v) W_o

``R_t``: rotary at position ``t`` over ADJACENT pairs ``(2i, 2i + 1)`` at the
plain frequencies ``rope_theta^(-2i/d)``. The model: the embedding (unscaled),
the blocks, ``n``, an untied head; float32 logits.

THE SHARE: this chip holds routed experts ``0 .. held - 1`` (``sz["held"]``:
the configuration's ``n_routed_experts`` as run) of the ``sz["experts"]`` the
router chooses among (the published count) beside all ``zero_expert_num``
identity experts (they have no weights: every chip computes them for its own
rows), and rows ``0 .. vocab - 1`` of the vocabulary. What an absent expert
would add is left out, here and in the program: the sum above runs over the
held experts only.

Leaves (bf16; ``expert_bias`` float32; made HERE from a seed in one jitted
call, under the names the program's model reads): ``wte.table``,
``ln_f.scale``, ``head.kernel``, and a block ``h<i>``: its two halves
``a0`` / ``a1``, each ``{ln1, ln2}.scale`` (the norm before its attention and
the one after), ``attn.{q_a_kernel, q_norm, q_b_kernel, kv_a_kernel, kv_norm,
kv_b_kernel, out_kernel}`` and ``{gate, up, down}.kernel`` (its dense
feed-forward), and ``moe.{router, expert_bias, gate, up, down}``; ``gate`` /
``up`` / ``down`` of ``moe`` are ``(held, expert_ffn_hidden_size, hidden)``:
gate and up "out x in", down "in x out".

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands and the rows q, c_kv, k_rope
are rounded (symmetric, one scale a row / an output column).

``without`` (``block``, ``Forward``): an equation left out or swapped for
its alternative, for the tests that show each one matters to the logits:
"shortcut" (no ``s``), "identity" (no identity term), "raw" (the chosen
weights renormalised to sum 1), "q_scale", "kv_scale", "second_cache" (the
second attention reads the FIRST one's latent rows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# twin columns of the head, as reference/mistral4.py has them: a greedy token
# only moves where two logits all but tie
TWIN_STD = 0.0256
# The cures of the two expert cells before this one, kept from the start
# (reference/mistral4.py and reference/afmoe.py say what each one cost to
# find): the embedding small, so that what a layer adds weighs as much as the
# token's own row; every router column at one norm, so that no expert is
# chosen more often by the draw alone, and the columns in opposed pairs
# ``w[2j + 1] = -w[2j]``, so that a vector every token's router input shares
# (attention's mean over a long context) gives one expert of a pair what it
# takes from the other. A pair lies wholly among the held experts, the absent
# ones or the zero-compute ones (all three counts are even), so the share of
# a token's picks that each kind takes keeps to its mean.
EMBED_STD = 0.02
NORM_STD = 0.02
# The columns' norm: the router's input is a normed row (root mean square 1),
# so its logits have this standard deviation. At 1.5 a token's 12 chosen
# probabilities run from ~0.011 to ~0.056 and sum to ~0.25: times
# ``routed_scaling_factor`` 6 a weight of ~1.5 a token over its 12 picks, of
# which a third lies on the identity experts. With a flat router (norm 0.1)
# the 12 would weigh 6 x 12 / 768 = 0.09 together and the whole expert layer
# would fall under bf16's rounding of the residual.
ROUTER_COLUMN_NORM = 1.5
# The selection bias: +-``BIAS`` beside probabilities of 0.01 to 0.06, so
# that the ids chosen (by p + bias) and their weights (by p) differ: at
# 0.0005 it changes the choice of ~45% of the tokens (at 0.01, afmoe's value
# beside sigmoid scores around 0.5, 91% of all picks would fall on the ids
# with a + and the router would be the bias). The SIGNS come from the seed,
# four + and four - in every run of ``BIAS_BLOCK`` ids, so that every chip's
# share of the experts and the zero-compute ids hold the same biases
# (reference/afmoe.py: drawn freely, six runs spread by 3.2%).
BIAS = 0.0005
BIAS_BLOCK = 8
# A greedy stream must not stand still: a token whose own column of the head
# lies within ``SELF_MARGIN`` of the best after a context of that token ALONE
# (``alone_forward``) gets that column with the opposite sign
# (reference/afmoe.py: two such tokens caught 11 of 32 rows in a window).
SELF_MARGIN = 1.0
ALONE_ROWS = 2048       # tokens a call of ``alone_forward``'s

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
              "num_attention_heads", "kv_lora_rank", "q_lora_rank",
              "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
              "moe_topk", "zero_expert_num")
Q_BLOCK = 512           # attention runs over this many queries at a time
H_GROUP = 8             # ... of this many heads
LENGTH_STEP = 2048      # a Forward is built for a multiple of this
WITHOUT = ("shortcut", "identity", "raw", "q_scale", "kv_scale",
           "second_cache")


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, the share (``held`` of
    ``experts``), and the two the harness reads: ``vocab_size`` (the rows
    held here) and ``positions`` (``served_positions``)."""
    sz = {k: int(cfg[k]) for k in (
        "num_layers", "hidden_size", "ffn_hidden_size",
        "expert_ffn_hidden_size", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "moe_topk", "zero_expert_num", "vocab_size",
        "max_position_embeddings")}
    sz["rms_norm_eps"] = float(cfg["rms_norm_eps"])
    sz["rope_theta"] = float(cfg["rope_theta"])
    sz["routed_scaling_factor"] = float(cfg["routed_scaling_factor"])
    sz["held"] = int(cfg["n_routed_experts"])
    sz["experts"] = int(cfg.get("published", {}).get("n_routed_experts",
                                                     sz["held"]))
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    if not (cfg.get("mla_scale_q_lora") and cfg.get("mla_scale_kv_lora")) \
            or cfg.get("zero_expert_type") != "identity" \
            or cfg.get("attention_method") != "MLA" \
            or cfg.get("attention_bias"):
        raise ValueError("this reference writes out bias-free latent "
                         "attention with both rank scales and identity "
                         "zero-compute experts")
    if sz["held"] % 2 or sz["experts"] % 2 or sz["zero_expert_num"] % 2:
        raise ValueError("the router's columns come in pairs: held, "
                         "published and zero-compute counts are even")
    return sz


def rank_scales(sz) -> tuple:
    """(mla_scale_q_lora, mla_scale_kv_lora) as numbers."""
    d = sz["hidden_size"]
    return (math.sqrt(d / sz["q_lora_rank"]),
            math.sqrt(d / sz["kv_lora_rank"]))


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    lat, exp = model.latent or {}, model.experts or {}
    got = dict(
        num_layers=model.num_layers, hidden_size=model.d_model,
        num_attention_heads=model.num_heads, vocab_size=model.vocab_size,
        ffn_hidden_size=model.mlp_hidden,
        q_lora_rank=lat.get("q_rank"), kv_lora_rank=lat.get("kv_rank"),
        qk_nope_head_dim=lat.get("nope_dim"),
        qk_rope_head_dim=lat.get("rope_dim"), v_head_dim=lat.get("v_dim"),
        rope_theta=float((lat.get("rope") or {}).get("rope_theta", 0.0)),
        expert_ffn_hidden_size=exp.get("hidden"),
        moe_topk=exp.get("top_k"), experts=exp.get("num_experts"),
        zero_expert_num=exp.get("zero_experts"),
        held=len(exp.get("held", ())),
        routed_scaling_factor=float(exp.get("route_scale", 1.0)),
        max_position_embeddings=model.max_len,
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    scales = (lat.get("q_scale"), lat.get("kv_scale"))
    if got != want or not getattr(model, "shortcut", False) \
            or exp.get("score") != "softmax_raw" or exp.get("shared") \
            or set(lat.get("rope") or {}) != {"rope_theta"} \
            or not np.allclose(scales, rank_scales(sz)) \
            or model.cache_layers != 2 * sz["num_layers"] \
            or list(exp.get("held", ())) != list(range(sz["held"])):
        raise SystemExit(f"the program's {name} has sizes {got}, rank scales "
                         f"{scales} and experts {exp}, the configuration "
                         f"file says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole steps of 2,048: runs whose longest request differs by less
    share one compiled program."""
    return -(-longest // LENGTH_STEP) * LENGTH_STEP


def param_shapes(sz: dict) -> dict:
    d, v, h = sz["hidden_size"], sz["vocab_size"], sz["num_attention_heads"]
    f, fe, n = (sz["ffn_hidden_size"], sz["expert_ffn_hidden_size"],
                sz["held"])
    width = sz["experts"] + sz["zero_expert_num"]
    qr, kr = sz["q_lora_rank"], sz["kv_lora_rank"]
    nope, rope, vd = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                      sz["v_head_dim"])
    half = {
        "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
        "attn": {"q_a_kernel": (d, qr), "q_norm": (qr,),
                 "q_b_kernel": (qr, h * (nope + rope)),
                 "kv_a_kernel": (d, kr + rope), "kv_norm": (kr,),
                 "kv_b_kernel": (kr, h * (nope + vd)),
                 "out_kernel": (h * vd, d)},
        "gate": {"kernel": (d, f)}, "up": {"kernel": (d, f)},
        "down": {"kernel": (f, d)}}
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)},
            "head": {"kernel": (d, v)}}
    for i in range(sz["num_layers"]):
        tree[f"h{i}"] = {
            "a0": half, "a1": half,
            "moe": {"router": (d, width), "expert_bias": (width,),
                    "gate": (n, fe, d), "up": (n, fe, d), "down": (n, fe, d)}}
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in; the selection
    bias float32). Normal, mean 0:

      wte ``EMBED_STD``; every matmul kernel 1/sqrt(fan_in) (an expert's gate
      and up: its last axis; its down: its middle axis), the projections
      back into the residual (attn.out_kernel, down.kernel, moe.down) a
      further 1/sqrt(4 blocks): four sublayers a block stand on the straight
      path; norm gains 1 + ``NORM_STD`` (so a dropped gain shows); a router's
      columns scaled to ``ROUTER_COLUMN_NORM`` and opposed in pairs;
      ``expert_bias`` +-``BIAS``, the signs from the seed and balanced in
      every ``BIAS_BLOCK`` ids.

    The head's twin columns (``TWIN_STD``) give the comparison near ties."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    back = 1.0 / math.sqrt(4 * sz["num_layers"])
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean, dtype = 0.0, jnp.bfloat16
        if name.endswith("wte/table"):
            std = EMBED_STD
        elif name.endswith(("scale", "q_norm", "kv_norm")):
            std, mean = NORM_STD, 1.0
        elif name.endswith("expert_bias"):
            std, dtype = BIAS, jnp.float32
        elif name.endswith(("moe/gate", "moe/up")):
            std = 1.0 / math.sqrt(shape[2])
        elif name.endswith("moe/down"):
            std = back / math.sqrt(shape[1])
        else:
            std = 1.0 / math.sqrt(shape[0])
            if name.endswith(("attn/out_kernel", "down/kernel")):
                std *= back
        how.append((shape, std, mean, dtype, name.rsplit("/", 1)[-1]))

    def build(key):
        out = []
        for i, (shape, std, mean, dtype, leaf) in enumerate(how):
            # a leaf at a time: a layer's experts drawn together would be
            # 2.4 GB of float32 before the cast
            x = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if leaf == "router":
                x *= ROUTER_COLUMN_NORM / jnp.linalg.norm(x, axis=0)
                x = x.at[:, 1::2].set(-x[:, 0::2])      # opposed pairs
            elif leaf == "expert_bias":
                # the upper half of every block's draws +, the lower half -
                blocks = x.reshape(-1, BIAS_BLOCK)
                rank = jnp.argsort(jnp.argsort(blocks, axis=1), axis=1)
                x = jnp.where(rank < BIAS_BLOCK // 2, -std, std).reshape(shape)
            out.append(x.astype(dtype))
        tree = jax.tree_util.tree_unflatten(treedef, out)
        half = sz["vocab_size"] // 2
        head = tree["head"]["kernel"].astype(jnp.float32)
        twins = head[:, :half] + TWIN_STD / math.sqrt(
            sz["hidden_size"]) * jax.random.normal(
            jax.random.fold_in(key, len(how)), (head.shape[0], half),
            jnp.float32)
        tree["head"]["kernel"] = head.at[:, half:2 * half].set(
            twins).astype(jnp.bfloat16)
        return tree

    tree = jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 63)))
    tree["head"]["kernel"] = _no_token_repeats_itself(tree, sz)
    return tree


def alone_forward(sz):
    """-> f(params, ids): next-token logits (len(ids), vocab) after a context
    that is ONE token (or any run of that one token), each of ``ids`` by
    itself."""
    layer = jax.jit(functools.partial(block, sz=sz, alone=True))
    last = jax.jit(functools.partial(head, sz=sz))

    def logits(params, ids):
        x = params["wte"]["table"][ids].astype(jnp.float32)
        for i in range(sz["num_layers"]):
            x = layer(params[f"h{i}"], x)
        return last(params, x, jnp.arange(len(ids)))

    return logits


def _no_token_repeats_itself(tree, sz):
    """The head with the column of every token of ``SELF_MARGIN`` negated."""
    v = sz["vocab_size"]
    rows = min(ALONE_ROWS, -(-v // Q_BLOCK) * Q_BLOCK)
    alone = alone_forward(sz)

    @jax.jit
    def sticks(logits, ids):
        own = jnp.take_along_axis(logits, ids[:, None], axis=1)[:, 0]
        others = logits.at[jnp.arange(len(ids)), ids].set(-jnp.inf)
        return own > jnp.max(others, axis=1) - SELF_MARGIN

    ids = np.arange(-(-v // rows) * rows, dtype=np.int32) % v
    stick = np.concatenate([np.asarray(sticks(alone(tree, c), c))
                            for c in jnp.asarray(ids.reshape(-1, rows))])[:v]
    sign = jnp.asarray(np.where(stick, -1.0, 1.0), jnp.bfloat16)
    return tree["head"]["kernel"] * sign


# ------------------------------------------------------------- forward ----

def _fake_int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fake_fp8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_ROUND = {"int8": _fake_int8, "fp8": _fake_fp8}


def _round(x, axis, quant):
    if quant is None:
        return x
    if quant not in _ROUND:
        raise ValueError(f"unknown control precision {quant!r}")
    return _ROUND[quant](x, axis)


def _matmul(x, w, quant):
    x = _round(x.astype(jnp.float32), -1, quant)
    w = _round(w.astype(jnp.float32), 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * g.astype(jnp.float32)


def rotary(x, positions, theta):
    """x (..., S, d), positions (S,): pairs (2i, 2i + 1) turned by
    ``positions * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
           ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_rows(p, n, sz, quant=None, without=()):
    """n (S, D) normed input -> the rows a cache would hold: c_kv (S,
    kv_lora_rank), normed and scaled, and k_rope (S, qk_rope_head_dim),
    turned."""
    kr = sz["kv_lora_rank"]
    kv = _matmul(n, p["kv_a_kernel"], quant)
    c_kv = rms_norm(kv[:, :kr], p["kv_norm"], sz["rms_norm_eps"])
    if "kv_scale" not in without:
        c_kv = c_kv * rank_scales(sz)[1]
    k_rope = rotary(kv[:, kr:], jnp.arange(n.shape[0]), sz["rope_theta"])
    return _round(c_kv, -1, quant), _round(k_rope, -1, quant)


def attention(p, n, rows, sz, quant=None, without=(), alone=False):
    """n (S, D) normed input, ``rows`` the latent rows it attends over ->
    (S, H * v_head_dim): the expanded form, a group of ``H_GROUP`` heads at a
    time. ``alone``: every position sees itself only (a context of one
    token: the softmax is 1 on its own value)."""
    s = n.shape[0]
    h = sz["num_attention_heads"]
    nope, rd, vd = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                    sz["v_head_dim"])
    kr, theta = sz["kv_lora_rank"], sz["rope_theta"]
    pos = jnp.arange(s)
    c_kv, k_rope = rows
    c_q = rms_norm(_matmul(n, p["q_a_kernel"], quant), p["q_norm"],
                   sz["rms_norm_eps"])
    q_scale = 1.0 if "q_scale" in without else rank_scales(sz)[0]
    scale = (nope + rd) ** -0.5
    qb, hg = min(Q_BLOCK, s), math.gcd(H_GROUP, h)
    w_q = p["q_b_kernel"].reshape(-1, h // hg, hg * (nope + rd))
    w_kv = p["kv_b_kernel"].reshape(kr, h // hg, hg * (nope + vd))

    def heads(i):
        kvb = _matmul(c_kv, w_kv[:, i], quant).reshape(s, hg, nope + vd)
        kvb = kvb.transpose(1, 0, 2)                            # (hg, S, .)
        v = kvb[..., nope:]
        if alone:
            return v.transpose(1, 0, 2).reshape(s, hg * vd)
        q = _matmul(c_q, w_q[:, i], quant).reshape(s, hg, nope + rd) * q_scale
        q = q.transpose(1, 0, 2)
        q = _round(jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], pos, theta)], -1), -1,
            quant)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_rope[None], (hg, s, rd))],
            -1)

        def block(j):
            qi = jax.lax.dynamic_slice_in_dim(q, j * qb, qb, axis=1)
            sc = jnp.einsum("hqd,hkd->hqk", qi, k, precision=HIGHEST) * scale
            qpos = j * qb + jnp.arange(qb)
            sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST)

        out = jax.lax.map(block, jnp.arange(s // qb))   # (S/qb, hg, qb, vd)
        return out.transpose(0, 2, 1, 3).reshape(s, hg * vd)

    out = jax.lax.map(heads, jnp.arange(h // hg))       # (H/hg, S, hg * vd)
    return out.transpose(1, 0, 2).reshape(s, h * vd)


def route(p, g, sz, without=()):
    """g (S, D) -> (S, experts + zero) float32: each token's weight on each
    of ALL the ids the router chooses among, zero off its top-k. Chosen by
    the probability plus the bias, weighted by the probability alone."""
    probs = jax.nn.softmax(jnp.matmul(g, p["router"].astype(jnp.float32),
                                      precision=HIGHEST), -1)
    _, ids = jax.lax.top_k(probs + p["expert_bias"].astype(jnp.float32),
                           sz["moe_topk"])
    w = jnp.take_along_axis(probs, ids, axis=-1)
    if "raw" in without:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * sz["routed_scaling_factor"]
    return jnp.zeros_like(probs).at[
        jnp.arange(g.shape[0])[:, None], ids].set(w)


def experts(p, g, sz, quant=None, which=None, without=()):
    """What the experts ``which`` (default: the held ones, ``0 .. held - 1``,
    leaf index = expert id) add for g (S, D), one expert at a time (each
    converted to float32 alone); NOT the identity term."""
    weights = route(p, g, sz, without)
    which = range(sz["held"]) if which is None else which

    def one(y, e_slot):
        e, slot = e_slot
        hid = jax.nn.silu(_matmul(g, p["gate"][slot].T, quant)) \
            * _matmul(g, p["up"][slot].T, quant)
        out = _matmul(hid, p["down"][slot], quant)
        return y + jnp.take(weights, e, axis=1)[:, None] * out, None

    ids = jnp.asarray(list(which), jnp.int32)
    y, _ = jax.lax.scan(one, jnp.zeros_like(g),
                        (ids, jnp.arange(len(ids), dtype=jnp.int32)))
    return y


def identity_term(p, g, sz, without=()):
    """What a token's picks among the zero-compute experts add: the sum of
    their weights times the token's own row."""
    weights = route(p, g, sz, without)
    return jnp.sum(weights[:, sz["experts"]:], axis=1, keepdims=True) * g


def gated_mlp(m, p, quant=None):
    hid = jax.nn.silu(_matmul(m, p["gate"]["kernel"], quant)) \
        * _matmul(m, p["up"]["kernel"], quant)
    return _matmul(hid, p["down"]["kernel"], quant)


def block(p, x, sz, quant=None, without=(), alone=False):
    """One block (two attentions, two dense feed-forwards, the shortcut
    expert layer) on x (S, D) float32."""
    eps = sz["rms_norm_eps"]
    p0, p1 = p["a0"], p["a1"]

    def mla(half, x, rows_of=None):
        n = rms_norm(x, half["ln1"]["scale"], eps)
        rows = latent_rows(half["attn"], n, sz, quant, without)
        return rows, x + _matmul(
            attention(half["attn"], n, rows_of or rows, sz, quant, without,
                      alone), half["attn"]["out_kernel"], quant)

    rows0, a0 = mla(p0, x)
    h0 = rms_norm(a0, p0["ln2"]["scale"], eps)
    s = jnp.zeros_like(x)
    if "shortcut" not in without:
        s = experts(p["moe"], h0, sz, quant, without=without)
        if "identity" not in without:
            s = s + identity_term(p["moe"], h0, sz, without)
    b0 = a0 + gated_mlp(h0, p0, quant)
    _, a1 = mla(p1, b0, rows0 if "second_cache" in without else None)
    return a1 + gated_mlp(rms_norm(a1, p1["ln2"]["scale"], eps), p1,
                          quant) + s


def head(p, x, pos, sz, quant=None):
    """Next-token logits (len(pos), vocab) float32 over the rows held."""
    n = rms_norm(x[pos], p["ln_f"]["scale"], sz["rms_norm_eps"])
    return _matmul(n, p["head"]["kernel"], quant)


class Forward:
    """Jitted, block-by-block logits of one sequence at a time, padded to one
    fixed length (causal, and an expert layer works a token at a time:
    padding never reaches an earlier position)."""

    def __init__(self, params, sz, length, quant=None, without=()):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        if length % min(Q_BLOCK, length):
            raise ValueError("a Forward is built for whole query blocks "
                             "(forward_length)")
        if set(without) - set(WITHOUT):
            raise ValueError(f"without names some of {WITHOUT}")
        self._embed = jax.jit(
            lambda p, ids: p["wte"]["table"][ids].astype(jnp.float32))
        self._block = jax.jit(functools.partial(
            block, sz=sz, quant=quant, without=tuple(without)))
        self._head = jax.jit(functools.partial(head, sz=sz, quant=quant))

    def rows(self, ids, positions):
        """Logits (len(positions), V) predicting token p + 1 for each p."""
        buf = np.zeros((self.length,), np.int32)
        buf[:len(ids)] = ids
        x = self._embed(self.params, jnp.asarray(buf))
        for i in range(self.sz["num_layers"]):
            x = self._block(self.params[f"h{i}"], x)
        # fixed shape: pad the positions to a step's multiple, cut after
        pos = np.zeros((-(-len(positions) // LENGTH_STEP) * LENGTH_STEP,),
                       np.int32)
        pos[:len(positions)] = positions
        out = self._head(self.params, x, jnp.asarray(pos))
        return np.asarray(out[:len(positions)])
