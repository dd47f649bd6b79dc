"""granite-4.0-h-micro (https://huggingface.co/ibm-granite/
granite-4.0-h-micro, config.json, ``model_type: granitemoehybrid``: 36
Mamba-2 layers and, at the layers the config's ``layer_types`` names, 4
plain grouped-query attention layers WITHOUT positions, a gated SiLU
feed-forward in every layer, no experts, four published multipliers) written
out in plain ``jax.numpy``: float32, matmul precision "highest", the
recurrence POSITION BY POSITION, no cache, no kernels, no pages, no slots. It
imports nothing of the program.

There is no network in this sandbox: every equation below is in the catalog
entry's ``config`` and ``described_as``
(``/opt/skills/guides/model-configs/architectures.jsonl``) or is recalled from
the family's public model code (``modeling_granitemoehybrid``, whose mixer is
``modeling_bamba``'s) and listed under ``assumed`` in the configuration file
with its alternative. A builder who knows the source to differ corrects THIS
file first; the program follows it.

``x`` is the float32 residual, ``W`` bias-free, ``n(.)`` RMSNorm with a PLAIN
gain, ``w x rsqrt(mean x^2 + eps)``. ``e``, ``r``, ``a``, ``s`` are the
config's ``embedding_multiplier``, ``residual_multiplier``,
``attention_multiplier`` and ``logits_scaling``. Layer ``l`` is of the kind
``layer_types[l]``:

    h_0 = e E[id];   u = h + r Mix_l(n1(h));   h' = u + r FF(n2(u))
    logits = n_f(h_L) E^T / s                   (tie_word_embeddings)

    Mamba-2 (H heads of P, a state of N a head, one group, taps = 4):
      [z | xBC | dt] = x W_in             (D -> H P + (H P + 2 N) + H)
      xBC <- silu(sum_j w_j * xBC_{t-taps+1+j} + b_c)   (causal, depthwise)
      [x | B | C] = xBC                   (B, C shared by all H heads)
      D_t = softplus(dt_t + dt_bias)      (a head; never clamped)
      S <- exp(-exp(A_log) D_t) S + D_t x_t B_t^T;  y_t = S C_t + D x_t
      g = y * silu(z);  out = (w_n * g rsqrt(mean g^2 + eps)) W_out
                                          (gate FIRST; ONE norm over H P)

    Attention (Hq query heads over Hkv KV heads of Dh): [q | k | v] = x
      W_qkv; NO rotary, no norm, no gate; causal softmax of a q.k; W_o

    FF(m) = W_d(silu(W_g m) * W_u m)      (the checkpoint fuses W_g | W_u)

Leaves (bf16; ``A_log``, ``dt_bias`` and ``D`` float32; made HERE from a seed
in one jitted call, under the names the program's model reads):
``wte.table`` (V, D), ``ln_f.scale``, and a layer ``h<i>``: ``{ln1,
ln2}.scale``, ``{gate, up, down}.kernel``; ``attn``: a Mamba layer's
``in_kernel`` (D, 2 H P + 2 N + H) laid ``[z | xBC | dt]``, ``conv_kernel``
(taps, H P + 2 N), ``conv_bias``, ``A_log``, ``dt_bias``, ``D`` (H,),
``norm`` (H P,), ``out_kernel`` (H P, D); an attention layer's
``qkv_kernel`` (D, (Hq + 2 Hkv) Dh) laid ``[q | k | v]``, ``out_kernel``.

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands and the rows q, k, v are
rounded (symmetric, one scale a row / an output column). ``"state_bf16"``
rounds nothing but the recurrent state, to bfloat16 after every position.

``without`` (``block``, ``Forward``): an equation left out or swapped for its
alternative, for the tests that show each one matters to the logits:
"skip" (no ``D x``), "conv_bias", "gate_first" (the norm before the gate),
"embedding", "residual", "logits" (that multiplier left at 1), "attention"
(the softmax at ``Dh^-1/2``), "decay" (``a = 1``). ``skip_update``: a
position whose state update is left out in every Mamba layer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# twin rows of the tied table, as reference/mistral4.py's head has twin
# columns: a greedy token only moves where two logits all but tie. RELATIVE
# to the table's own deviation
TWIN_SHARE = 0.0256
NORM_STD = 0.02
BIAS_STD = 0.5          # the convolution's bias: beside a unit pre-activation
# The head is the embedding: after a context that ends in token v the logit
# of v itself holds e |E_v|^2 / rms(h_L) more than the others, whose spread
# is sqrt(D) std(E). With the sublayers' outputs near unit size the stream's
# rms is about r sqrt(1.4 L) (a mixer ~1, a feed-forward ~0.6), so the self
# term is (e sqrt(D) std(E) / that) deviations: the table is drawn small
# enough that it is ``SELF_SHARE`` of one, and no token holds a greedy
# stream for ever. (An untied head is repaired after the fact,
# reference/qwen3_next._no_token_repeats_itself; a tied one cannot be.)
SELF_SHARE = 0.5
# The family's initial ranges (``assumed``): A uniform in [A_MIN, A_MAX), dt
# log-uniform in [DT_MIN, DT_MAX], dt_bias its inverse softplus, D = 1. A
# head's decay then lies between ~0.2 and ~0.999 a position.
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 0.001, 0.1

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "shared_intermediate_size", "intermediate_size",
              "mamba_d_head", "mamba_d_state", "mamba_n_heads",
              "mamba_expand", "mamba_d_conv", "mamba_n_groups",
              "num_attention_heads", "num_key_value_heads")
Q_BLOCK = 512           # attention runs over this many queries at a time
LENGTH_STEP = 2048      # a Forward is built for a multiple of this
HEAD_BLOCKS = 8         # the head runs over this many blocks of columns
KINDS = ("mamba", "attention")
WITHOUT = ("skip", "conv_bias", "gate_first", "embedding", "residual",
           "logits", "attention", "decay")


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, ``layer_types`` as
    published, the four multipliers, and the two the harness reads:
    ``vocab_size`` and ``positions`` (``served_positions``)."""
    sz = {k: int(cfg[k]) for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "shared_intermediate_size", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
        "mamba_expand", "vocab_size", "max_position_embeddings")}
    for k in ("rms_norm_eps", "embedding_multiplier", "residual_multiplier",
              "attention_multiplier", "logits_scaling"):
        sz[k] = float(cfg[k])
    sz["head_dim"] = sz["hidden_size"] // sz["num_attention_heads"]
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    sz["layer_types"] = list(cfg["layer_types"])
    if len(sz["layer_types"]) != sz["num_hidden_layers"] \
            or set(sz["layer_types"]) - set(KINDS):
        raise ValueError(f"layer_types names one of {KINDS} for each of "
                         "the num_hidden_layers layers")
    if cfg.get("position_embedding_type") != "nope" \
            or cfg.get("num_local_experts") or cfg.get("num_experts_per_tok") \
            or not cfg.get("mamba_conv_bias") or cfg.get("mamba_proj_bias") \
            or cfg.get("attention_bias") or cfg.get("rope_scaling") \
            or not cfg.get("tie_word_embeddings") \
            or cfg.get("hidden_act") != "silu" \
            or cfg.get("normalization_function") != "rmsnorm" \
            or sz["mamba_n_groups"] != 1:
        raise ValueError("this reference writes out attention without "
                         "positions or biases, no experts, a convolution "
                         "with a bias, bias-free projections, one group of "
                         "B and C, SiLU, RMSNorm and a tied head")
    if sz["mamba_expand"] * sz["hidden_size"] \
            != sz["mamba_n_heads"] * sz["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is mamba_n_heads x "
                         "mamba_d_head")
    return sz


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    lin = getattr(model, "linear", None) or {}
    scales = getattr(model, "scales", None) or {}
    got = dict(
        num_hidden_layers=model.num_layers, hidden_size=model.d_model,
        num_attention_heads=model.num_heads,
        num_key_value_heads=model.num_kv_heads, vocab_size=model.vocab_size,
        shared_intermediate_size=model.mlp_hidden,
        layer_types=list(getattr(model, "layer_types", None) or ()),
        mamba_n_heads=lin.get("heads"), mamba_d_head=lin.get("head_dim"),
        mamba_d_state=lin.get("state"), mamba_d_conv=lin.get("conv"),
        embedding_multiplier=scales.get("embedding"),
        residual_multiplier=scales.get("residual"),
        attention_multiplier=scales.get("attention"),
        logits_scaling=scales.get("logits"),
        max_position_embeddings=model.max_len,
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    if got != want or lin.get("mixer") != "mamba2" or model.rope_theta \
            or model.gated or model.experts or model.latent \
            or model.norm_unit_offset or not model.tie_embeddings \
            or model.cache_layers != sz["layer_types"].count(KINDS[1]):
        raise SystemExit(f"the program's {name} has sizes {got} and state "
                         f"mixer {lin}, the configuration file says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole steps of 2,048: runs whose longest request differs by less
    share one compiled program."""
    return -(-longest // LENGTH_STEP) * LENGTH_STEP


def param_shapes(sz: dict) -> dict:
    d, v, f = sz["hidden_size"], sz["vocab_size"], \
        sz["shared_intermediate_size"]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    heads, n = sz["mamba_n_heads"], sz["mamba_d_state"]
    inner = heads * sz["mamba_d_head"]
    channels = inner + 2 * n
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)}}
    for i, kind in enumerate(sz["layer_types"]):
        layer = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
                 "gate": {"kernel": (d, f)}, "up": {"kernel": (d, f)},
                 "down": {"kernel": (f, d)}}
        if kind == "mamba":
            layer["attn"] = {
                "in_kernel": (d, inner + channels + heads),
                "conv_kernel": (sz["mamba_d_conv"], channels),
                "conv_bias": (channels,), "A_log": (heads,),
                "dt_bias": (heads,), "D": (heads,), "norm": (inner,),
                "out_kernel": (inner, d)}
        else:
            layer["attn"] = {"qkv_kernel": (d, (h + 2 * hkv) * dh),
                             "out_kernel": (h * dh, d)}
        tree[f"h{i}"] = layer
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def embed_std(sz: dict) -> float:
    """The tied table's deviation (``SELF_SHARE`` says why it is small)."""
    stream = sz["residual_multiplier"] * math.sqrt(
        1.4 * sz["num_hidden_layers"])
    return SELF_SHARE * stream / (sz["embedding_multiplier"]
                                  * math.sqrt(sz["hidden_size"]))


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in; ``A_log``,
    ``dt_bias`` and ``D`` float32). Normal, mean 0:

      wte ``embed_std(sz)``, its upper half twin rows of its lower; every
      matmul kernel 1/sqrt(fan_in) (the convolution: its taps), with NO
      further factor on the projections back into the residual: the
      model's own ``residual_multiplier`` stands there; every gain (the
      norms' and the mixer's gated norm) ``NORM_STD`` around 1, so that a
      dropped gain shows; the convolution's bias ``BIAS_STD``; ``A_log`` /
      ``dt_bias`` in the family's initial ranges and ``D`` = 1 (module
      constants)."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean, dtype = 0.0, jnp.bfloat16
        if name.endswith("wte/table"):
            std = embed_std(sz)
        elif name.endswith(("scale", "attn/norm")):
            std, mean = NORM_STD, 1.0
        elif name.endswith("conv_bias"):
            std = BIAS_STD
        elif name.endswith(("A_log", "dt_bias", "attn/D")):
            std, dtype = 1.0, jnp.float32       # drawn or set below
        else:
            std = 1.0 / math.sqrt(shape[0])
        how.append((shape, std, mean, dtype, name.rsplit("/", 1)[-1]))

    def build(key):
        out = []
        for i, (shape, std, mean, dtype, leaf) in enumerate(how):
            k = jax.random.fold_in(key, i)
            if leaf == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               A_MIN, A_MAX))
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(DT_MIN),
                    math.log(DT_MAX)))
                x = dt + jnp.log(-jnp.expm1(-dt))       # inverse softplus
            elif leaf == "D":
                x = jnp.ones(shape, jnp.float32)
            else:
                x = mean + std * jax.random.normal(k, shape, jnp.float32)
            out.append(x.astype(dtype))
        tree = jax.tree_util.tree_unflatten(treedef, out)
        half = sz["vocab_size"] // 2
        table = tree["wte"]["table"].astype(jnp.float32)
        twins = table[:half] + TWIN_SHARE * embed_std(sz) * jax.random.normal(
            jax.random.fold_in(key, len(how)), (half, table.shape[1]),
            jnp.float32)
        tree["wte"]["table"] = table.at[half:2 * half].set(twins).astype(
            jnp.bfloat16)
        return tree

    return jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 63)))


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


def rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * g.astype(jnp.float32)


def mamba(p, n, sz, quant=None, without=(), skip_update=None):
    """n (S, D) normed input -> (S, D): the Mamba-2 mixer, its recurrence a
    ``lax.scan`` over the positions, one at a time."""
    s = n.shape[0]
    heads, hp, ns = sz["mamba_n_heads"], sz["mamba_d_head"], \
        sz["mamba_d_state"]
    taps, inner = sz["mamba_d_conv"], heads * hp
    channels = inner + 2 * ns
    zxd = _matmul(n, p["in_kernel"], quant)
    z, u, dt = (zxd[:, :inner], zxd[:, inner:inner + channels],
                zxd[:, inner + channels:])
    ext = jnp.concatenate([jnp.zeros((taps - 1, channels), u.dtype), u])
    w = p["conv_kernel"].astype(jnp.float32)
    c = sum(w[j] * ext[j:j + s] for j in range(taps))
    if "conv_bias" not in without:
        c = c + p["conv_bias"].astype(jnp.float32)
    c = jax.nn.silu(c)
    x = _round(c[:, :inner].reshape(s, heads, hp), -1, quant)
    bm = _round(c[:, inner:inner + ns], -1, quant)
    cm = _round(c[:, inner + ns:], -1, quant)
    step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # (S, H)
    if skip_update is not None:
        step = jnp.where((jnp.arange(s) != skip_update)[:, None], step, 0.0)
    decay = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * step)
    if "decay" in without:
        decay = jnp.ones_like(decay)

    def position(state, t):
        xt, bt, ct, st, at = t          # (H, P), (N,), (N,), (H,), (H,)
        state = state * at[:, None, None] \
            + (st[:, None] * xt)[:, :, None] * bt[None, None, :]
        if quant == "state_bf16":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hpn,n->hp", state, ct, precision=HIGHEST)

    _, y = jax.lax.scan(position, jnp.zeros((heads, hp, ns), jnp.float32),
                        (x, bm, cm, step, decay))
    if "skip" not in without:
        y = y + p["D"].astype(jnp.float32)[:, None] * x
    y, gate = y.reshape(s, inner), jax.nn.silu(z)
    if "gate_first" in without:
        g = rms_norm(y, p["norm"], sz["rms_norm_eps"]) * gate
    else:
        g = rms_norm(y * gate, p["norm"], sz["rms_norm_eps"])
    return _matmul(g, p["out_kernel"], quant)


def attention(p, n, sz, quant=None, without=()):
    """n (S, D) normed input -> (S, D): no positions, no norm, no gate; one
    KV head and its group of query heads at a time, ``Q_BLOCK`` queries at a
    time."""
    s = n.shape[0]
    h, hkv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                  sz["head_dim"])
    g = h // hkv
    scale = dh ** -0.5 if "attention" in without \
        else sz["attention_multiplier"]
    pos = jnp.arange(s)
    qkv = _matmul(n, p["qkv_kernel"], quant)
    q, k, v = jnp.split(qkv, [h * dh, (h + hkv) * dh], axis=-1)
    q = q.reshape(s, h, dh).transpose(1, 0, 2)
    k = k.reshape(s, hkv, dh).transpose(1, 0, 2)
    v = v.reshape(s, hkv, dh).transpose(1, 0, 2)
    q, k, v = (_round(t, -1, quant) for t in (q, k, v))
    q = q.reshape(hkv, g, s, dh)
    qb = min(Q_BLOCK, s)

    def group(i):
        qi, ki, vi = q[i], k[i], v[i]               # (g, S, dh), (S, dh) x 2

        def some(j):
            qj = jax.lax.dynamic_slice_in_dim(qi, j * qb, qb, axis=1)
            sc = jnp.einsum("gqd,kd->gqk", qj, ki, precision=HIGHEST) * scale
            qpos = (j * qb + jnp.arange(qb))[:, None]
            sc = jnp.where((pos[None, :] <= qpos)[None], sc, -jnp.inf)
            return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), vi,
                              precision=HIGHEST)

        out = jax.lax.map(some, jnp.arange(s // qb))    # (S/qb, g, qb, dh)
        return out.transpose(1, 0, 2, 3).reshape(g, s, dh)

    out = jax.lax.map(group, jnp.arange(hkv))           # (hkv, g, S, dh)
    out = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)
    return _matmul(out, p["out_kernel"], quant)


def feed_forward(p, m, quant=None):
    hid = jax.nn.silu(_matmul(m, p["gate"]["kernel"], quant)) \
        * _matmul(m, p["up"]["kernel"], quant)
    return _matmul(hid, p["down"]["kernel"], quant)


def block(p, x, sz, kind, quant=None, without=(), skip_update=None):
    """One decoder layer on x (S, D) float32."""
    eps = sz["rms_norm_eps"]
    r = 1.0 if "residual" in without else sz["residual_multiplier"]
    a = rms_norm(x, p["ln1"]["scale"], eps)
    if kind == "mamba":
        x = x + r * mamba(p["attn"], a, sz, quant, without, skip_update)
    else:
        x = x + r * attention(p["attn"], a, sz, quant, without)
    return x + r * feed_forward(p, rms_norm(x, p["ln2"]["scale"], eps), quant)


def embed(p, ids, sz, without=()):
    x = p["wte"]["table"][ids].astype(jnp.float32)
    return x if "embedding" in without else x * sz["embedding_multiplier"]


def head(p, x, pos, first, sz, width, quant=None, without=()):
    """Next-token logits (len(pos), width) float32 over the ``width`` rows
    of the tied table from row ``first`` on."""
    n = rms_norm(x[pos], p["ln_f"]["scale"], sz["rms_norm_eps"])
    rows = jax.lax.dynamic_slice_in_dim(p["wte"]["table"], first, width)
    out = _matmul(n, rows.T, quant)
    return out if "logits" in without else out / sz["logits_scaling"]


class Forward:
    """Jitted, layer-by-layer logits of one sequence at a time, padded to one
    fixed length (causal, and the recurrence runs forward: padding never
    reaches an earlier position). The head runs over ``HEAD_BLOCKS`` blocks
    of the table's rows, so that no float32 copy of the whole table stands
    beside the weights."""

    def __init__(self, params, sz, length, quant=None, without=(),
                 skip_update=None):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        if length % min(Q_BLOCK, length):
            raise ValueError("a Forward is built for whole query blocks "
                             "(forward_length)")
        if set(without) - set(WITHOUT):
            raise ValueError(f"without names some of {WITHOUT}")
        without = tuple(without)
        self._embed = jax.jit(functools.partial(embed, sz=sz,
                                                without=without))
        # one program a kind of layer
        self._block = {kind: jax.jit(functools.partial(
            block, sz=sz, kind=kind, quant=quant, without=without,
            skip_update=skip_update)) for kind in KINDS}
        v = sz["vocab_size"]
        self._width = -(-v // HEAD_BLOCKS)
        self._head = jax.jit(functools.partial(
            head, sz=sz, width=self._width, quant=quant, without=without))

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
        pos, v = jnp.asarray(pos), self.sz["vocab_size"]
        out = np.empty((len(positions), v), np.float32)
        for first in range(0, v, self._width):
            # the last block starts early enough to be whole
            at = min(first, v - self._width)
            part = self._head(self.params, x, pos, at)
            out[:, at:at + self._width] = np.asarray(part[:len(positions)])
        return out
