"""Trinity Large's decoder (https://huggingface.co/arcee-ai/Trinity-Large-Preview,
config.json: ``model_type: "afmoe"``: sliding-window layers beside global
layers, gated grouped-query attention with a norm on every query and key
head, leading dense layers, then 256 routed experts beside one shared,
sigmoid routing with a selection bias) written out in plain ``jax.numpy``:
float32, matmul precision "highest", no cache, no kernels, no pages. It
imports nothing of the program.

There is no network in this sandbox: every equation below is in the catalog
entry's ``config`` and ``described_as``
(``/opt/skills/guides/model-configs/architectures.jsonl``) or is listed under
``assumed`` in the configuration file with its alternative. A builder who
knows the source to differ corrects THIS file first; the program follows it.

``x`` is the float32 residual, ``W`` bias-free, ``rms(.)`` RMSNorm with a plain
gain and ``eps`` = ``rms_norm_eps``:

    h0 = embed[ids] * sqrt(hidden_size)                          (mup_enabled)
    a  = rms_in(x);  [q | k | v | g] = a W_qkvg
         q, g: num_attention_heads x head_dim; k, v: num_key_value_heads x head_dim
    q, k = rms_q(q), rms_k(k)   a head, over head_dim
    sliding_attention: q, k = rope(q, k)  (rope_theta, half rotation, all
         head_dim); full_attention: NO positions
    s_ij = q_i . k_j / sqrt(head_dim),  j <= i  and (full or i - j < sliding_window)
    o  = (softmax(s) v) * sigmoid(g);     x += rms_post_attn(o W_o)
    m  = rms_pre_mlp(x)
    dense (layer < num_dense_layers):  f = (silu(m W_gate) * (m W_up)) W_down
    expert: p = sigmoid(m W_r)  float32, over ALL num_experts
            E = the num_experts_per_tok largest of p + expert_bias
            w_e = route_scale * p_e / (sum_{e in E} p_e + 1e-20)   (route_norm)
            f = shared(m) + sum_{e in E} w_e expert_e(m)
    x += rms_post_mlp(f);     logits = rms_f(x) W_head            (untied)

THE SHARE: this chip holds routed experts ``0 .. held - 1`` (``sz["held"]``:
the configuration's ``num_experts`` as run) of the ``sz["experts"]`` the router
chooses among (the published count), and rows ``0 .. vocab - 1`` of the
vocabulary. What an absent expert would add is left out, here and in the
program: the sum above runs over the held experts only.

Leaves (bf16; ``expert_bias`` float32; made HERE from a seed in one jitted
call, under the names the program's model reads): ``wte.table``,
``ln_f.scale``, ``head.kernel``, ``h<i>.{ln1, ln1_post, ln2, ln2_post}.scale``,
``h<i>.attn.{qkvg_kernel, q_norm, k_norm, out_kernel}``, a dense layer's
``h<i>.{gate, up, down}.kernel``, an expert layer's ``h<i>.moe.{router,
expert_bias, gate, up, down, shared_gate, shared_up, shared_down}``; ``gate``
/ ``up`` / ``down`` of ``moe`` are ``(held, moe_intermediate_size, hidden)``:
gate and up "out x in", down "in x out".

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands and the rows q, k, v are
rounded (symmetric, one scale a row / an output column).
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
# PR 32's cures, kept from the start (reference/mistral4.py says why): the
# embedding small, so that what a layer adds weighs as much as the token's
# own row (here the muP scale sqrt(hidden) brings it to ~1.1 beside sublayer
# outputs that their post-norms hold at ~1); every router column at one norm,
# so that no expert is chosen more often by the draw alone.
EMBED_STD = 0.02
NORM_STD = 0.02
ROUTER_COLUMN_NORM = 1.0
# Two more of the same kind, found on this cell (PERF.md section 2, PR 37).
# Over a long context of random tokens attention's output is nearly the SAME
# small vector for every token (a mean of thousands of values); a post-norm
# at gain 1 blows it up to the size of everything else in the residual, every
# token's router input then shares that vector, an expert is chosen by how
# its column lies to it (popularity's coefficient of variation ~1), and the
# 32 held experts took 12.0% to 13.9% of the assignments from seed to seed:
# six runs spread by 2.0%. So the post-ATTENTION norm's gain is drawn around
# ``POST_ATTN_GAIN`` (a trained model's attention is no such mean; its
# depth-scaled gains start small too), and the router's columns come in
# opposed pairs, ``w[2j + 1] = -w[2j]``: what a shared vector gives one
# expert of a pair it takes from the other, so a chip's share of the experts
# keeps its share of the assignments to first order.
POST_ATTN_GAIN = 0.1
# The selection bias: +-``BIAS`` (small beside the scores' spread: a normed
# input of sqrt(hidden) against a unit column gives logits of std ~1), so
# that the experts chosen (by p + bias) and their weights (by p) differ: at
# 0.01 it changes the choice of 47% of the tokens. Its SIGNS come from the
# seed, four + and four - in every run of ``BIAS_BLOCK`` experts, so that
# every chip's share of the experts holds the same biases: drawn freely
# (normal, std 0.05, this PR's first weights) the 32 held experts took 10.5%
# to 15.6% of the assignments from seed to seed, a step read 8.9 to 11.2 of
# them a layer, and six runs of the cell spread by 3.2% (PERF.md section 2,
# PR 37). A trained router's bias is what HOLDS the loads equal; this is the
# random router's stand-in for that, as the columns' one norm is.
BIAS = 0.01
BIAS_BLOCK = 8
# A greedy stream must not stand still. After a run of ONE token every key
# and value a layer sees is that token's, so the state is what the token
# gives ALONE (``alone_forward``), and where its own column of the head comes
# first there the stream repeats it for ever; attention's small share of the
# residual (``POST_ATTN_GAIN``) makes the basin wide. Among 25,024 columns
# drawn freely about one such token is expected a seed. At seed 3700990404
# two caught 11 of the cell's 32 rows inside the window: rows on one token
# choose the same experts, ``experts_hit_share`` read 0.346 for 0.39 and the
# run 2,509.8 tokens/s for 2,432, twice over (PERF.md section 2, PR 37). So
# a token whose own column lies within ``SELF_MARGIN`` of the best (logits
# have std ~1; the context moves them by ~0.1 to 0.2) gets that column with
# the opposite sign, a handful of columns a seed: nothing repeats a token
# by the head's draw alone, as a trained head does not.
SELF_MARGIN = 1.0
ALONE_ROWS = 2048       # tokens a call of ``alone_forward``'s

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "num_shared_experts", "sliding_window", "num_attention_heads",
              "num_key_value_heads")
Q_BLOCK = 512           # attention runs over this many queries at a time
LENGTH_STEP = 2048      # a Forward is built for a multiple of this
KINDS = ("sliding_attention", "full_attention")


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, the share (``held`` of
    ``experts``), and the two the harness reads: ``vocab_size`` (the rows
    held here) and ``positions`` (``served_positions``)."""
    sz = {k: int(cfg[k]) for k in (
        "num_hidden_layers", "num_dense_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "num_shared_experts", "sliding_window", "vocab_size",
        "max_position_embeddings")}
    sz["rms_norm_eps"] = float(cfg["rms_norm_eps"])
    sz["rope_theta"] = float(cfg["rope_theta"])
    sz["route_scale"] = float(cfg.get("route_scale", 1.0))
    sz["mup_enabled"] = bool(cfg.get("mup_enabled", False))
    sz["layer_types"] = [str(k) for k in cfg["layer_types"]]
    sz["held"] = int(cfg["num_experts"])
    sz["experts"] = int(cfg.get("published", {}).get("num_experts",
                                                     sz["held"]))
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    if len(sz["layer_types"]) != sz["num_hidden_layers"] \
            or set(sz["layer_types"]) - set(KINDS):
        raise ValueError("layer_types names one of "
                         f"{KINDS} for each of the layers as run")
    if cfg.get("score_func") != "sigmoid" or not cfg.get("route_norm") \
            or cfg.get("n_group", 1) != 1 or cfg.get("rope_scaling"):
        raise ValueError("this reference writes out sigmoid scores, a "
                         "renormalised top-k, no expert groups and plain "
                         "rotary frequencies")
    return sz


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    gat, exp = model.gated or {}, model.experts or {}
    got = dict(
        num_hidden_layers=model.num_layers, hidden_size=model.d_model,
        num_attention_heads=model.num_heads,
        num_key_value_heads=model.num_kv_heads, vocab_size=model.vocab_size,
        head_dim=gat.get("head_dim"), sliding_window=gat.get("window"),
        layer_types=list(gat.get("layer_types", ())),
        rope_theta=float(gat.get("rope_theta", 0.0)),
        num_dense_layers=model.num_dense_layers,
        intermediate_size=model.dense_hidden,
        moe_intermediate_size=exp.get("hidden"),
        num_experts_per_tok=exp.get("top_k"),
        num_shared_experts=exp.get("shared"), experts=exp.get("num_experts"),
        held=len(exp.get("held", ())),
        route_scale=float(exp.get("route_scale", 1.0)),
        max_position_embeddings=model.max_len,
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    if got != want or exp.get("score") != "sigmoid" \
            or list(exp.get("held", ())) != list(range(sz["held"])) \
            or bool(model.embed_scale) != sz["mup_enabled"]:
        raise SystemExit(f"the program's {name} has sizes {got}, the "
                         f"configuration file says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole steps of 2,048: runs whose longest request differs by less
    share one compiled program."""
    return -(-longest // LENGTH_STEP) * LENGTH_STEP


def param_shapes(sz: dict) -> dict:
    d, v = sz["hidden_size"], sz["vocab_size"]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    f, e, n = sz["moe_intermediate_size"], sz["experts"], sz["held"]
    fd, fs = sz["intermediate_size"], sz["num_shared_experts"] * f
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)},
            "head": {"kernel": (d, v)}}
    for i in range(sz["num_hidden_layers"]):
        layer = {name: {"scale": (d,)}
                 for name in ("ln1", "ln1_post", "ln2", "ln2_post")}
        layer["attn"] = {"qkvg_kernel": (d, (2 * h + 2 * hkv) * dh),
                         "q_norm": (dh,), "k_norm": (dh,),
                         "out_kernel": (h * dh, d)}
        if i < sz["num_dense_layers"]:
            layer.update(gate={"kernel": (d, fd)}, up={"kernel": (d, fd)},
                         down={"kernel": (fd, d)})
        else:
            layer["moe"] = {"router": (d, e), "expert_bias": (e,),
                            "gate": (n, f, d), "up": (n, f, d),
                            "down": (n, f, d), "shared_gate": (d, fs),
                            "shared_up": (d, fs), "shared_down": (fs, d)}
        tree[f"h{i}"] = layer
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in; the selection
    bias float32). Normal, mean 0:

      wte ``EMBED_STD``; every matmul kernel 1/sqrt(fan_in) (an expert's gate
      and up: its last axis; its down: its middle axis); norm gains 1 +
      ``NORM_STD`` (so a dropped gain shows), the post-attention norm's
      around ``POST_ATTN_GAIN``; a router's columns scaled to
      ``ROUTER_COLUMN_NORM`` and opposed in pairs; ``expert_bias`` +-``BIAS``, the signs from the
      seed and balanced in every ``BIAS_BLOCK`` experts. No residual
      projection is scaled down by depth: every sublayer's output goes
      through a norm of its own before it is added.

    The head's twin columns (``TWIN_STD``) give the comparison near ties."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean, dtype = 0.0, jnp.bfloat16
        if name.endswith("wte/table"):
            std = EMBED_STD
        elif name.endswith("ln1_post/scale"):
            std, mean = NORM_STD * POST_ATTN_GAIN, POST_ATTN_GAIN
        elif name.endswith(("scale", "q_norm", "k_norm")):
            std, mean = NORM_STD, 1.0
        elif name.endswith("expert_bias"):
            std, dtype = BIAS, jnp.float32
        elif name.endswith(("moe/gate", "moe/up")):
            std = 1.0 / math.sqrt(shape[2])
        elif name.endswith("moe/down"):
            std = 1.0 / math.sqrt(shape[1])
        else:
            std = 1.0 / math.sqrt(shape[0])
        how.append((shape, std, mean, dtype, name.rsplit("/", 1)[-1]))

    def build(key):
        out = []
        for i, (shape, std, mean, dtype, leaf) in enumerate(how):
            # a leaf at a time: a layer's experts drawn together would be
            # 3.6 GB of float32 before the cast
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
    itself. A program a kind of layer, as ``Forward`` has them."""
    first = jax.jit(functools.partial(embed, sz=sz))
    layer = jax.jit(functools.partial(block, sz=sz, kind="alone"))
    last = jax.jit(functools.partial(head, sz=sz))

    def logits(params, ids):
        x = first(params, ids)
        for i in range(sz["num_hidden_layers"]):
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
    """x (..., S, d), positions (S,): the half rotation, value ``i`` paired
    with value ``i + d / 2``, turned by ``positions * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv.astype(np.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, n, sz, kind, quant=None):
    """n (S, D) normed input -> (S, H * head_dim), gated: one KV head and its
    group of query heads at a time, ``Q_BLOCK`` queries at a time."""
    s = n.shape[0]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    g, eps = h // hkv, sz["rms_norm_eps"]
    pos = jnp.arange(s)
    qkvg = _matmul(n, p["qkvg_kernel"], quant)
    q, k, v, gate = jnp.split(
        qkvg, [h * dh, (h + hkv) * dh, (h + 2 * hkv) * dh], axis=-1)
    q = rms_norm(q.reshape(s, h, dh), p["q_norm"], eps).transpose(1, 0, 2)
    k = rms_norm(k.reshape(s, hkv, dh), p["k_norm"], eps).transpose(1, 0, 2)
    v = v.reshape(s, hkv, dh).transpose(1, 0, 2)
    if kind == "sliding_attention":
        q = rotary(q, pos, sz["rope_theta"])
        k = rotary(k, pos, sz["rope_theta"])
    q, k, v = (_round(t, -1, quant) for t in (q, k, v))
    q = q.reshape(hkv, g, s, dh)
    qb = min(Q_BLOCK, s)
    window = sz["sliding_window"]

    def group(i):
        qi, ki, vi = q[i], k[i], v[i]               # (g, S, dh), (S, dh) x 2

        def block(j):
            qj = jax.lax.dynamic_slice_in_dim(qi, j * qb, qb, axis=1)
            sc = jnp.einsum("gqd,kd->gqk", qj, ki, precision=HIGHEST) \
                / math.sqrt(dh)
            qpos = (j * qb + jnp.arange(qb))[:, None]
            seen = pos[None, :] <= qpos
            if kind == "sliding_attention":
                seen &= qpos - pos[None, :] < window
            elif kind == "alone":       # each row a context of its own
                seen = pos[None, :] == qpos
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), vi,
                              precision=HIGHEST)

        out = jax.lax.map(block, jnp.arange(s // qb))   # (S/qb, g, qb, dh)
        return out.transpose(1, 0, 2, 3).reshape(g, s, dh)

    out = jax.lax.map(group, jnp.arange(hkv))           # (hkv, g, S, dh)
    out = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)
    return out * jax.nn.sigmoid(gate)


def route(p, m, sz):
    """m (S, D) -> (S, experts) float32: each token's weight on each of ALL
    the experts the router chooses among, zero off its top-k. Chosen by the
    score plus the bias, weighted by the score alone."""
    scores = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(jnp.float32),
                                       precision=HIGHEST))
    _, ids = jax.lax.top_k(scores + p["expert_bias"].astype(jnp.float32),
                           sz["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * sz["route_scale"]
    return jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], ids].set(w)


def experts(p, m, sz, quant=None, which=None):
    """What the experts ``which`` (default: the held ones, ``0 .. held - 1``,
    leaf index = expert id) add for m (S, D), one expert at a time (each
    converted to float32 alone), plus nothing shared."""
    weights = route(p, m, sz)
    which = range(sz["held"]) if which is None else which

    def one(y, e_slot):
        e, slot = e_slot
        hid = jax.nn.silu(_matmul(m, p["gate"][slot].T, quant)) \
            * _matmul(m, p["up"][slot].T, quant)
        out = _matmul(hid, p["down"][slot], quant)
        return y + jnp.take(weights, e, axis=1)[:, None] * out, None

    ids = jnp.asarray(list(which), jnp.int32)
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (ids, jnp.arange(len(ids), dtype=jnp.int32)))
    return y


def gated_mlp(m, gate, up, down, quant=None):
    hid = jax.nn.silu(_matmul(m, gate, quant)) * _matmul(m, up, quant)
    return _matmul(hid, down, quant)


def shared(p, m, quant=None):
    return gated_mlp(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                     quant)


def block(p, x, sz, kind, quant=None):
    """One decoder layer on x (S, D) float32: dense where it has no ``moe``."""
    eps = sz["rms_norm_eps"]
    a = rms_norm(x, p["ln1"]["scale"], eps)
    o = _matmul(attention(p["attn"], a, sz, kind, quant),
                p["attn"]["out_kernel"], quant)
    x = x + rms_norm(o, p["ln1_post"]["scale"], eps)
    m = rms_norm(x, p["ln2"]["scale"], eps)
    if "moe" in p:
        f = experts(p["moe"], m, sz, quant)
        if sz["num_shared_experts"]:
            f = f + shared(p["moe"], m, quant)
    else:
        f = gated_mlp(m, p["gate"]["kernel"], p["up"]["kernel"],
                      p["down"]["kernel"], quant)
    return x + rms_norm(f, p["ln2_post"]["scale"], eps)


def embed(p, ids, sz):
    x = p["wte"]["table"][ids].astype(jnp.float32)
    return x * math.sqrt(sz["hidden_size"]) if sz["mup_enabled"] else x


def head(p, x, pos, sz, quant=None):
    """Next-token logits (len(pos), vocab) float32 over the rows held."""
    n = rms_norm(x[pos], p["ln_f"]["scale"], sz["rms_norm_eps"])
    return _matmul(n, p["head"]["kernel"], quant)


class Forward:
    """Jitted, layer-by-layer logits of one sequence at a time, padded to one
    fixed length (causal, and an expert layer works a token at a time:
    padding never reaches an earlier position)."""

    def __init__(self, params, sz, length, quant=None):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        if length % min(Q_BLOCK, length):
            raise ValueError("a Forward is built for whole query blocks "
                             "(forward_length)")
        self._embed = jax.jit(functools.partial(embed, sz=sz))
        # one program a kind of layer (and dense or expert: by the leaves)
        self._block = {kind: jax.jit(functools.partial(
            block, sz=sz, kind=kind, quant=quant)) for kind in KINDS}
        self._head = jax.jit(functools.partial(head, sz=sz, quant=quant))

    def rows(self, ids, positions):
        """Logits (len(positions), V) predicting token p + 1 for each p."""
        buf = np.zeros((self.length,), np.int32)
        buf[:len(ids)] = ids
        x = self._embed(self.params, jnp.asarray(buf))
        for i, kind in enumerate(self.sz["layer_types"]):
            x = self._block[kind](self.params[f"h{i}"], x)
        # fixed shape: pad the positions to a step's multiple, cut after
        pos = np.zeros((-(-len(positions) // LENGTH_STEP) * LENGTH_STEP,),
                       np.int32)
        pos[:len(positions)] = positions
        out = self._head(self.params, x, jnp.asarray(pos))
        return np.asarray(out[:len(positions)])
