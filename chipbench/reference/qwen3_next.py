"""Qwen3-Next-80B-A3B-Instruct (https://huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct, config.json, ``model_type: qwen3_next``: three
Gated DeltaNet layers to one gated full-attention layer, 512 small experts at
10 a token beside a gated shared expert) written out in plain ``jax.numpy``:
float32, matmul precision "highest", the recurrence POSITION BY POSITION, no
cache, no kernels, no pages, no slots. It imports nothing of the program. The
multi-token-prediction head has no key in the catalog's ``config`` and is
not served.

There is no network in this sandbox: every equation below is in the catalog
entry's ``config`` and ``described_as``
(``/opt/skills/guides/model-configs/architectures.jsonl``) or is recalled from
the family's public model code (``modeling_qwen3_next``) and listed under
``assumed`` in the configuration file with its alternative. A builder who
knows the source to differ corrects THIS file first; the program follows it.

``x`` is the float32 residual, ``W`` bias-free, ``n(.)`` RMSNorm with a UNIT
OFFSET, ``x rsqrt(mean x^2 + eps) (1 + w)``. Layer ``l`` is linear unless
``(l + 1) % full_attention_interval == 0``:

    h = x + Mix_l(n1(x));   y = h + MoE(n2(h))

    Gated DeltaNet (Hk key heads of Dk, Hv value heads of Dv, taps = 4):
      [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
      u = [q | k | v];  c_t = silu(sum_j w_j * u_{t-taps+1+j})   (causal, depthwise)
      q <- q rsqrt(|q|^2 + 1e-6) Dk^-1/2;  k <- k rsqrt(|k|^2 + 1e-6)   (a head;
           key head j serves value heads j Hv/Hk .. (j + 1) Hv/Hk - 1)
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
      S <- e^g S;  r = S^T k;  d = beta (v - r);  S <- S + k d^T;  o = S^T q
      y = (w * o rsqrt(mean o^2 + eps) * silu(z)) W_o        (a head; plain gain)

    Gated full attention (H query heads over Hkv KV heads of Dh):
      [q | k | v | gate] = x W_qkvg;  q, k <- n(q), n(k) a head (unit offset)
      the FIRST partial_rotary_factor Dh lanes of q and k turned by half
      rotation at theta; causal softmax at Dh^-1/2;  (a * sigmoid(gate)) W_o

    MoE(m): p = softmax(m W_r) float32 over ALL num_experts; the
      num_experts_per_tok largest, weights p / sum_top p (norm_topk_prob);
      sum_e w_e W_d(silu(W_g m) * W_u m)  +  sigmoid(m . w_sg) E_shared(m)

The model: the embedding (unscaled), the layers, ``n``, an untied head;
float32 logits.

THE SHARE: this chip holds routed experts ``0 .. held - 1`` (``sz["held"]``:
the configuration's ``num_experts`` as run) of the ``sz["experts"]`` the
router chooses among (the published count), the shared expert whole, and rows
``0 .. vocab - 1`` of the vocabulary. What an absent expert would add is left
out, here and in the program.

Leaves (bf16; ``A_log`` and ``dt_bias`` float32; made HERE from a seed in one
jitted call, under the names the program's model reads): ``wte.table``,
``ln_f.scale``, ``head.kernel``, and a layer ``h<i>``: ``{ln1, ln2}.scale``;
``attn``: a linear layer's ``qkvz_kernel`` (D, 2 Hk Dk + 2 Hv Dv, laid ``[q |
k | v | z]``, each by head), ``ba_kernel`` (D, 2 Hv), ``conv_kernel`` (taps,
2 Hk Dk + Hv Dv), ``A_log``, ``dt_bias`` (Hv,), ``norm`` (Dv,),
``out_kernel``; a full layer's ``qkvg_kernel`` (D, (2 H + 2 Hkv) Dh, laid
``[q | k | v | gate]``), ``q_norm``, ``k_norm`` (Dh,), ``out_kernel``; ``moe``:
``router`` (D, experts), ``gate`` / ``up`` / ``down`` (held, F, D), gate and
up "out x in", down "in x out", ``shared_gate`` / ``shared_up`` (D, Fs),
``shared_down`` (Fs, D), ``shared_router`` (D, 1).

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands and the rows q, k, v are
rounded (symmetric, one scale a row / an output column). ``"state_bf16"``
rounds nothing but the recurrent state, to bfloat16 after every position.

``without`` (``block``, ``Forward``): an equation left out or swapped for its
alternative, for the tests that show each one matters to the logits:
"shared_gate" (the shared expert ungated), "partial_rotary" (the whole head
turned), "unit_offset" (plain gains), "decay" (``g = 0``), "conv" (no
convolution: ``c = silu(u)``). ``skip_update``: a position whose state update
is left out in every linear layer.
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
# What the three expert cells before this one paid for (reference/mistral4.py,
# afmoe.py and longcat_flash.py say what each cost to find), kept from the
# start: the embedding small; every router column at one norm and the columns
# in opposed pairs ``w[2j + 1] = -w[2j]`` (a pair lies wholly among the held
# experts or the absent ones: both counts are even); no token whose own
# column of the head wins after that token alone. The model has no selection
# bias, so none is drawn.
EMBED_STD = 0.02
NORM_STD = 0.02
ROUTER_COLUMN_NORM = 1.0
SELF_MARGIN = 1.0
ALONE_ROWS = 2048       # tokens a call of ``alone_forward``'s
# The family's initial ranges (``assumed``): A uniform in [A_MIN, A_MAX), dt
# log-uniform in [DT_MIN, DT_MAX], dt_bias its inverse softplus. With a ~
# N(0, 1) a head's decay e^g then lies between ~0.2 and ~0.9999 a position:
# heads that forget in a few tokens beside heads that hold thousands. The
# gated norm a head takes the state's SIZE out of the layer's output, so no
# head dies or swamps the residual whatever its decay.
A_MIN, A_MAX = 0.01, 16.0
DT_MIN, DT_MAX = 0.001, 0.1

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "linear_conv_kernel_dim",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "partial_rotary_factor", "full_attention_interval")
Q_BLOCK = 512           # attention runs over this many queries at a time
LENGTH_STEP = 2048      # a Forward is built for a multiple of this
KINDS = ("linear_attention", "full_attention")
WITHOUT = ("shared_gate", "partial_rotary", "unit_offset", "decay", "conv")


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, the share (``held`` of
    ``experts``), and the two the harness reads: ``vocab_size`` (the rows
    held here) and ``positions`` (``served_positions``)."""
    sz = {k: int(cfg[k]) for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "full_attention_interval",
        "linear_conv_kernel_dim", "linear_key_head_dim",
        "linear_value_head_dim", "linear_num_key_heads",
        "linear_num_value_heads", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_experts_per_tok",
        "vocab_size", "max_position_embeddings")}
    sz["rms_norm_eps"] = float(cfg["rms_norm_eps"])
    sz["rope_theta"] = float(cfg["rope_theta"])
    sz["rotary_dim"] = int(round(float(cfg["partial_rotary_factor"])
                                 * sz["head_dim"]))
    sz["held"] = int(cfg["num_experts"])
    sz["experts"] = int(cfg.get("published", {}).get("num_experts",
                                                     sz["held"]))
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    sz["layer_types"] = [
        KINDS[(i + 1) % sz["full_attention_interval"] == 0]
        for i in range(sz["num_hidden_layers"])]
    if not cfg.get("norm_topk_prob") or cfg.get("decoder_sparse_step") != 1 \
            or cfg.get("mlp_only_layers") or cfg.get("rope_scaling") \
            or cfg.get("tie_word_embeddings") \
            or cfg.get("hidden_act") != "silu":
        raise ValueError("this reference writes out a renormalised top-k, an "
                         "expert layer in every block, plain rotary "
                         "frequencies, SiLU and an untied head")
    if sz["held"] % 2 or sz["experts"] % 2:
        raise ValueError("the router's columns come in pairs: held and "
                         "published counts are even")
    return sz


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    gat, exp, lin = model.gated or {}, model.experts or {}, model.linear or {}
    got = dict(
        num_hidden_layers=model.num_layers, hidden_size=model.d_model,
        num_attention_heads=model.num_heads,
        num_key_value_heads=model.num_kv_heads, vocab_size=model.vocab_size,
        head_dim=gat.get("head_dim"), rotary_dim=gat.get("rotary_dim"),
        layer_types=list(gat.get("layer_types", ())),
        rope_theta=float(gat.get("rope_theta", 0.0)),
        linear_num_key_heads=lin.get("key_heads"),
        linear_num_value_heads=lin.get("value_heads"),
        linear_key_head_dim=lin.get("key_dim"),
        linear_value_head_dim=lin.get("value_dim"),
        linear_conv_kernel_dim=lin.get("conv"),
        moe_intermediate_size=exp.get("hidden"),
        shared_expert_intermediate_size=exp.get("hidden", 0)
        * exp.get("shared", 0),
        num_experts_per_tok=exp.get("top_k"), experts=exp.get("num_experts"),
        held=len(exp.get("held", ())),
        max_position_embeddings=model.max_len,
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    if got != want or exp.get("score", "softmax") != "softmax" \
            or not exp.get("shared_gated") or not gat.get("rope_full") \
            or not gat.get("norm_unit_offset") or gat.get("window") \
            or not model.norm_unit_offset or model.tie_embeddings \
            or model.cache_layers != sz["layer_types"].count(KINDS[1]) \
            or list(exp.get("held", ())) != list(range(sz["held"])):
        raise SystemExit(f"the program's {name} has sizes {got}, attention "
                         f"{gat} and experts {exp}, the configuration file "
                         f"says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole steps of 2,048: runs whose longest request differs by less
    share one compiled program."""
    return -(-longest // LENGTH_STEP) * LENGTH_STEP


def param_shapes(sz: dict) -> dict:
    d, v = sz["hidden_size"], sz["vocab_size"]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    f, fs = sz["moe_intermediate_size"], sz["shared_expert_intermediate_size"]
    e, n = sz["experts"], sz["held"]
    channels = 2 * hk * dk + hv * dv
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)},
            "head": {"kernel": (d, v)}}
    for i, kind in enumerate(sz["layer_types"]):
        layer = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)}}
        if kind == "linear_attention":
            layer["attn"] = {
                "qkvz_kernel": (d, channels + hv * dv),
                "ba_kernel": (d, 2 * hv),
                "conv_kernel": (sz["linear_conv_kernel_dim"], channels),
                "A_log": (hv,), "dt_bias": (hv,), "norm": (dv,),
                "out_kernel": (hv * dv, d)}
        else:
            layer["attn"] = {"qkvg_kernel": (d, (2 * h + 2 * hkv) * dh),
                             "q_norm": (dh,), "k_norm": (dh,),
                             "out_kernel": (h * dh, d)}
        layer["moe"] = {"router": (d, e), "gate": (n, f, d), "up": (n, f, d),
                        "down": (n, f, d), "shared_gate": (d, fs),
                        "shared_up": (d, fs), "shared_down": (fs, d),
                        "shared_router": (d, 1)}
        tree[f"h{i}"] = layer
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in; ``A_log`` and
    ``dt_bias`` float32). Normal, mean 0:

      wte ``EMBED_STD``; every matmul kernel 1/sqrt(fan_in) (an expert's gate
      and up: its last axis; its down: its middle axis; the convolution: its
      taps), the projections back into the residual (attn.out_kernel,
      moe.down, moe.shared_down) a further 1/sqrt(2 layers): two sublayers a
      layer stand on the straight path; a unit-offset gain ``NORM_STD``
      around 0, the linear layers' plain gain (``norm``) around 1 (so a
      dropped gain shows); a router's columns scaled to
      ``ROUTER_COLUMN_NORM`` and opposed in pairs; ``A_log`` / ``dt_bias`` in
      the family's initial ranges (module constants).

    The head's twin columns (``TWIN_STD``) give the comparison near ties."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    back = 1.0 / math.sqrt(2 * sz["num_hidden_layers"])
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean, dtype = 0.0, jnp.bfloat16
        if name.endswith("wte/table"):
            std = EMBED_STD
        elif name.endswith(("scale", "q_norm", "k_norm")):
            std = NORM_STD                      # unit offset: around 0
        elif name.endswith("attn/norm"):
            std, mean = NORM_STD, 1.0
        elif name.endswith(("A_log", "dt_bias")):
            std, dtype = 1.0, jnp.float32       # drawn uniformly below
        elif name.endswith(("moe/gate", "moe/up")):
            std = 1.0 / math.sqrt(shape[2])
        elif name.endswith("moe/down"):
            std = back / math.sqrt(shape[1])
        else:
            std = 1.0 / math.sqrt(shape[0])
            if name.endswith(("attn/out_kernel", "shared_down")):
                std *= back
        how.append((shape, std, mean, dtype, name.rsplit("/", 1)[-1]))

    def build(key):
        out = []
        for i, (shape, std, mean, dtype, leaf) in enumerate(how):
            # a leaf at a time: a layer's experts drawn together would be
            # 0.8 GB of float32 before the cast
            k = jax.random.fold_in(key, i)
            if leaf == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               A_MIN, A_MAX))
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(DT_MIN),
                    math.log(DT_MAX)))
                x = dt + jnp.log(-jnp.expm1(-dt))       # inverse softplus
            else:
                x = mean + std * jax.random.normal(k, shape, jnp.float32)
            if leaf == "router":
                x *= ROUTER_COLUMN_NORM / jnp.linalg.norm(x, axis=0)
                x = x.at[:, 1::2].set(-x[:, 0::2])      # opposed pairs
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
    that is ONE token, each of ``ids`` by itself: the layers over sequences
    of length 1, side by side."""
    layer = {kind: jax.jit(jax.vmap(functools.partial(
        block, sz=sz, kind=kind), in_axes=(None, 0))) for kind in KINDS}
    last = jax.jit(functools.partial(head, sz=sz))

    def logits(params, ids):
        x = params["wte"]["table"][ids].astype(jnp.float32)[:, None, :]
        for i, kind in enumerate(sz["layer_types"]):
            x = layer[kind](params[f"h{i}"], x)
        return last(params, x[:, 0], jnp.arange(len(ids)))

    return logits


def _no_token_repeats_itself(tree, sz):
    """The head with the column of every token of ``SELF_MARGIN`` negated: a
    token whose own column lies within the margin of the best after a
    context of that token alone would hold a greedy stream for ever."""
    v = sz["vocab_size"]
    rows = min(ALONE_ROWS, -(-v // 8) * 8)
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
    if quant is None or quant == "state_bf16":
        return x
    if quant not in _ROUND:
        raise ValueError(f"unknown control precision {quant!r}")
    return _ROUND[quant](x, axis)


def _matmul(x, w, quant):
    x = _round(x.astype(jnp.float32), -1, quant)
    w = _round(w.astype(jnp.float32), 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, g, eps, unit_offset=True):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    g = g.astype(jnp.float32)
    return x / jnp.sqrt(ms + eps) * (1.0 + g if unit_offset else g)


def rotary(x, positions, theta):
    """x (..., S, d), positions (S,): the half rotation, value ``i`` paired
    with value ``i + d / 2``, turned by ``positions * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv.astype(np.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def delta_net(p, n, sz, quant=None, without=(), skip_update=None):
    """n (S, D) normed input -> (S, D): the Gated DeltaNet layer, its
    recurrence a ``lax.scan`` over the positions, one at a time."""
    s = n.shape[0]
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    taps, qk = sz["linear_conv_kernel_dim"], hk * dk
    channels = 2 * qk + hv * dv
    qkvz = _matmul(n, p["qkvz_kernel"], quant)
    ba = _matmul(n, p["ba_kernel"], quant)
    u, z = qkvz[:, :channels], qkvz[:, channels:]
    if "conv" in without:
        c = jax.nn.silu(u)
    else:
        ext = jnp.concatenate([jnp.zeros((taps - 1, channels), u.dtype), u])
        w = p["conv_kernel"].astype(jnp.float32)
        c = jax.nn.silu(sum(w[j] * ext[j:j + s] for j in range(taps)))

    def unit(t):
        t = t.reshape(s, hk, dk)
        t = t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(t, hv // hk, axis=1)              # a value head

    q = _round(unit(c[:, :qk]) * dk ** -0.5, -1, quant)
    k = _round(unit(c[:, qk:2 * qk]), -1, quant)
    v = _round(c[:, 2 * qk:].reshape(s, hv, dv), -1, quant)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
    if "decay" in without:
        g = jnp.zeros_like(g)
    if skip_update is not None:
        keep = (jnp.arange(s) != skip_update)[:, None]
        g, beta = jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)

    def position(state, t):
        qt, kt, vt, gt, bt = t                      # (Hv, .), (Hv,)
        state = state * jnp.exp(gt)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", state, kt, precision=HIGHEST)
        d = bt[:, None] * (vt - r)
        state = state + kt[:, :, None] * d[:, None, :]
        if quant == "state_bf16":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HIGHEST)

    _, o = jax.lax.scan(position, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = rms_norm(o, p["norm"], sz["rms_norm_eps"], unit_offset=False)
    y = o.reshape(s, hv * dv) * jax.nn.silu(z)
    return _matmul(y, p["out_kernel"], quant)


def attention(p, n, sz, quant=None, without=()):
    """n (S, D) normed input -> (S, D), gated: one KV head and its group of
    query heads at a time, ``Q_BLOCK`` queries at a time."""
    s = n.shape[0]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    g, eps = h // hkv, sz["rms_norm_eps"]
    unit = "unit_offset" not in without
    rd = dh if "partial_rotary" in without else sz["rotary_dim"]
    pos = jnp.arange(s)
    qkvg = _matmul(n, p["qkvg_kernel"], quant)
    q, k, v, gate = jnp.split(
        qkvg, [h * dh, (h + hkv) * dh, (h + 2 * hkv) * dh], axis=-1)
    q = rms_norm(q.reshape(s, h, dh), p["q_norm"], eps, unit)
    k = rms_norm(k.reshape(s, hkv, dh), p["k_norm"], eps, unit)
    q, k = q.transpose(1, 0, 2), k.transpose(1, 0, 2)
    v = v.reshape(s, hkv, dh).transpose(1, 0, 2)
    q, k = (jnp.concatenate([rotary(t[..., :rd], pos, sz["rope_theta"]),
                             t[..., rd:]], axis=-1) for t in (q, k))
    q, k, v = (_round(t, -1, quant) for t in (q, k, v))
    q = q.reshape(hkv, g, s, dh)
    qb = min(Q_BLOCK, s)

    def group(i):
        qi, ki, vi = q[i], k[i], v[i]               # (g, S, dh), (S, dh) x 2

        def some(j):
            qj = jax.lax.dynamic_slice_in_dim(qi, j * qb, qb, axis=1)
            sc = jnp.einsum("gqd,kd->gqk", qj, ki, precision=HIGHEST) \
                / math.sqrt(dh)
            qpos = (j * qb + jnp.arange(qb))[:, None]
            sc = jnp.where((pos[None, :] <= qpos)[None], sc, -jnp.inf)
            return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), vi,
                              precision=HIGHEST)

        out = jax.lax.map(some, jnp.arange(s // qb))    # (S/qb, g, qb, dh)
        return out.transpose(1, 0, 2, 3).reshape(g, s, dh)

    out = jax.lax.map(group, jnp.arange(hkv))           # (hkv, g, S, dh)
    out = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)
    return _matmul(out * jax.nn.sigmoid(gate), p["out_kernel"], quant)


def route(p, m, sz):
    """m (S, D) -> (S, experts) float32: each token's weight on each of ALL
    the experts the router chooses among, zero off its top-k; the chosen
    probabilities renormalised to sum 1."""
    probs = jax.nn.softmax(jnp.matmul(m, p["router"].astype(jnp.float32),
                                      precision=HIGHEST), -1)
    w, ids = jax.lax.top_k(probs, sz["num_experts_per_tok"])
    w = w / jnp.sum(w, -1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(m.shape[0])[:, None], ids].set(w)


def experts(p, m, sz, quant=None, which=None):
    """What the experts ``which`` (default: the held ones, ``0 .. held - 1``,
    leaf index = expert id) add for m (S, D), one expert at a time (each
    converted to float32 alone); nothing shared."""
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


def shared(p, m, quant=None, without=()):
    """The shared expert times its token's sigmoid gate."""
    hid = jax.nn.silu(_matmul(m, p["shared_gate"], quant)) \
        * _matmul(m, p["shared_up"], quant)
    out = _matmul(hid, p["shared_down"], quant)
    if "shared_gate" in without:
        return out
    return out * jax.nn.sigmoid(jnp.matmul(
        m, p["shared_router"].astype(jnp.float32), precision=HIGHEST))


def block(p, x, sz, kind, quant=None, without=(), skip_update=None):
    """One decoder layer on x (S, D) float32."""
    eps, unit = sz["rms_norm_eps"], "unit_offset" not in without
    a = rms_norm(x, p["ln1"]["scale"], eps, unit)
    if kind == "linear_attention":
        x = x + delta_net(p["attn"], a, sz, quant, without, skip_update)
    else:
        x = x + attention(p["attn"], a, sz, quant, without)
    m = rms_norm(x, p["ln2"]["scale"], eps, unit)
    return x + experts(p["moe"], m, sz, quant) \
        + shared(p["moe"], m, quant, without)


def head(p, x, pos, sz, quant=None):
    """Next-token logits (len(pos), vocab) float32 over the rows held."""
    n = rms_norm(x[pos], p["ln_f"]["scale"], sz["rms_norm_eps"])
    return _matmul(n, p["head"]["kernel"], quant)


class Forward:
    """Jitted, layer-by-layer logits of one sequence at a time, padded to one
    fixed length (causal, the recurrence runs forward, and an expert layer
    works a token at a time: padding never reaches an earlier position)."""

    def __init__(self, params, sz, length, quant=None, without=(),
                 skip_update=None):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        if length % min(Q_BLOCK, length):
            raise ValueError("a Forward is built for whole query blocks "
                             "(forward_length)")
        if set(without) - set(WITHOUT):
            raise ValueError(f"without names some of {WITHOUT}")
        self._embed = jax.jit(
            lambda p, ids: p["wte"]["table"][ids].astype(jnp.float32))
        # one program a kind of layer
        self._block = {kind: jax.jit(functools.partial(
            block, sz=sz, kind=kind, quant=quant, without=tuple(without),
            skip_update=skip_update)) for kind in KINDS}
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
