"""Mistral Small 4's decoder (https://huggingface.co/mistralai/
Mistral-Small-4-119B-2603, config.json: ``model_type: "mistral4"``, latent
attention, 128 routed experts beside one shared) written out in plain
``jax.numpy``: float32, matmul precision "highest", the EXPANDED form of the
attention (every head's keys and values made from the latent row: no
absorption), no cache, no kernels, no pages. It imports nothing of the
program.

There is no network in this sandbox: every equation below is in the catalog
entry's ``config`` (``/opt/skills/guides/model-configs/architectures.jsonl``)
or is listed under ``assumed`` in the configuration file with its
alternative. A builder who knows the source to differ corrects THIS file
first; the program follows it.

One layer; ``x`` is the float32 residual, ``eps`` = ``rms_norm_eps``, RMSNorm's
gain is its ``scale`` (no unit offset), no biases:

    h = RMSNorm(x)
    c_q = RMSNorm(h W_qa)                       (q_lora_rank)
    q   = c_q W_qb  -> heads of [q_nope | q_rope]   (qk_nope_head_dim | qk_rope_head_dim)
    [c_kv | k_r] = h W_kva                      (kv_lora_rank | qk_rope_head_dim)
    c_kv = RMSNorm(c_kv);  k_rope = R_t k_r  (ONE for all heads);  q_rope = R_t q_rope
    [k_nope | v] = c_kv W_kvb  a head           (qk_nope_head_dim | v_head_dim)
    s = (q_nope . k_nope + q_rope . k_rope) * qk_head_dim^-1/2 * m^2
    o = causal_softmax(s) v;   x += concat(o) W_o

``R_t``: rotary at position ``t`` over ADJACENT pairs ``(2i, 2i + 1)``
(``rope_interleave``) at YaRN frequencies: ``theta^(-2i/d)`` for the pairs
that turn more than ``beta_fast`` times over the ``original`` positions, that
over ``factor`` for those that turn less than ``beta_slow`` times, a linear
ramp between. ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (``mscale`` =
``mscale_all_dim``, so cos and sin carry no further factor). The query of
position ``t`` is first scaled by ``1 + llama_4_scaling_beta * ln(1 +
floor(t / original))``.

    g = RMSNorm(x)
    p = softmax(g W_g)  over ALL n_routed_experts, float32
    the num_experts_per_tok largest, renormalised to sum 1, times routed_scaling_factor
    x += sum_e p_e W_down,e(silu(W_gate,e g) * W_up,e g) + shared(g)

THE SHARE: this chip holds routed experts ``0 .. held - 1`` (``sz["held"]``:
the configuration's ``n_routed_experts`` as run) of the ``sz["experts"]`` the
router chooses among (the published count), and rows ``0 .. vocab - 1`` of the
vocabulary. What an absent expert would add is left out, here and in the
program: the sum above runs over the held experts only.

Leaves (bf16, made HERE from a seed in one jitted call, under the names the
program's model reads): ``wte.table``, ``ln_f.scale``, ``head.kernel``,
``h<i>.{ln1,ln2}.scale``, ``h<i>.attn.{q_a_kernel, q_norm, q_b_kernel,
kv_a_kernel, kv_norm, kv_b_kernel, out_kernel}``, ``h<i>.moe.{router, gate,
up, down, shared_gate, shared_up, shared_down}``; ``gate`` / ``up`` / ``down``
are ``(held, moe_intermediate_size, hidden)``: gate and up "out x in", down
"in x out".

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands and the rows q, c_kv, k_rope
are rounded (symmetric, one scale a row / an output column).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# twin columns of the head, as reference/evabyte.py has them: a greedy token
# only moves where two logits all but tie
TWIN_STD = 0.0256
# The embedding at 0.02 (as reference/gpt2.py has it), NOT at 1: the first
# RMSNorm takes the scale out anyway, but at 1 the residual stream stays the
# token's own embedding (a layer's attention adds ~0.02 to it), so the next
# token AND every layer's choice of experts are functions of the current
# token alone, greedy decoding falls onto a fixed token a row within a few
# hundred steps, and a window's expert load is 32 fixed choices: it read 2.2%
# apart between seeds on the chip (PERF.md section 2, PR 32). At 0.02 the
# context weighs as much as the token from the first layer on.
EMBED_STD = 0.02
NORM_STD = 0.02
# A router's columns all have the norm 1 (what their 1/sqrt(fan_in) draw has
# on the mean). An expert whose column is 1% longer has logits 1% wider and
# is among the top 4 of 128 about 3.5% more often, so with the columns as
# drawn the held experts' share of the assignments read 0.2472 and 0.2496 on
# two seeds (0.74% apart by the draw alone), the experts HIT moved with it,
# and six seeds read 0.6% apart in steps a window (PERF.md section 2, PR 32).
# A trained router is held to equal loads by its auxiliary loss; this is the
# random router's stand-in for it.
ROUTER_COLUMN_NORM = 1.0

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "qk_head_dim", "head_dim", "moe_intermediate_size",
              "intermediate_size", "num_experts_per_tok", "n_shared_experts",
              "num_attention_heads", "num_key_value_heads")
Q_BLOCK = 512           # attention runs over this many queries at a time
H_GROUP = 8             # ... of this many heads
LENGTH_STEP = 2048      # a Forward is built for a multiple of this


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, the share
    (``held`` of ``experts``), and the two the harness reads: ``vocab_size``
    (the rows held here) and ``positions`` (``served_positions``)."""
    sz = {k: int(cfg[k]) for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
        "num_experts_per_tok", "n_shared_experts", "vocab_size",
        "max_position_embeddings")}
    sz["rms_norm_eps"] = float(cfg["rms_norm_eps"])
    sz["routed_scaling_factor"] = float(cfg.get("routed_scaling_factor", 1))
    sz["held"] = int(cfg["n_routed_experts"])
    sz["experts"] = int(cfg.get("published", {}).get("n_routed_experts",
                                                     sz["held"]))
    sz["rope"] = {k: v for k, v in cfg["rope_parameters"].items()
                  if isinstance(v, (int, float))}
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    if cfg.get("first_k_dense_replace", 0) or cfg.get("n_group", 1) != 1 \
            or not cfg.get("norm_topk_prob", True):
        raise ValueError("this reference writes out no dense first layers, "
                         "no expert groups and a renormalised top-k")
    return sz


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    lat, exp = model.latent or {}, model.experts or {}
    rope = dict(lat.get("rope") or {})
    got = dict(
        num_hidden_layers=model.num_layers, hidden_size=model.d_model,
        num_attention_heads=model.num_heads, vocab_size=model.vocab_size,
        q_lora_rank=lat.get("q_rank"), kv_lora_rank=lat.get("kv_rank"),
        qk_nope_head_dim=lat.get("nope_dim"),
        qk_rope_head_dim=lat.get("rope_dim"), v_head_dim=lat.get("v_dim"),
        moe_intermediate_size=exp.get("hidden"),
        num_experts_per_tok=exp.get("top_k"),
        n_shared_experts=exp.get("shared"), experts=exp.get("num_experts"),
        held=len(exp.get("held", ())),
        max_position_embeddings=model.max_len,
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    same_rope = all(float(rope.get(k, float("nan"))) == float(v)
                    for k, v in sz["rope"].items())
    if got != want or not same_rope \
            or list(exp.get("held", ())) != list(range(sz["held"])):
        raise SystemExit(f"the program's {name} has sizes {got} and rotary "
                         f"{rope}, the configuration file says {want} and "
                         f"{sz['rope']}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole steps of 2,048: runs whose longest request differs by less
    share one compiled program."""
    return -(-longest // LENGTH_STEP) * LENGTH_STEP


def param_shapes(sz: dict) -> dict:
    d, v, h = sz["hidden_size"], sz["vocab_size"], sz["num_attention_heads"]
    f, e, n = sz["moe_intermediate_size"], sz["experts"], sz["held"]
    fs = sz["n_shared_experts"] * f
    qr, kr = sz["q_lora_rank"], sz["kv_lora_rank"]
    nope, rope, vd = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                      sz["v_head_dim"])
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)},
            "head": {"kernel": (d, v)}}
    for i in range(sz["num_hidden_layers"]):
        tree[f"h{i}"] = {
            "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
            "attn": {"q_a_kernel": (d, qr), "q_norm": (qr,),
                     "q_b_kernel": (qr, h * (nope + rope)),
                     "kv_a_kernel": (d, kr + rope), "kv_norm": (kr,),
                     "kv_b_kernel": (kr, h * (nope + vd)),
                     "out_kernel": (h * vd, d)},
            "moe": {"router": (d, e), "gate": (n, f, d), "up": (n, f, d),
                    "down": (n, f, d), "shared_gate": (d, fs),
                    "shared_up": (d, fs), "shared_down": (fs, d)}}
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in). Normal, mean 0:

      wte ``EMBED_STD`` (see there); every matmul kernel 1/sqrt(fan_in) (an expert's gate
      and up: its last axis; its down: its middle axis), the residual
      projections (attn.out_kernel, moe.down, moe.shared_down) a further
      1/sqrt(2 layers); norm gains 1 + ``NORM_STD`` (so a dropped gain shows);
      a router's columns scaled to ``ROUTER_COLUMN_NORM`` (see there).

    The head's twin columns (``TWIN_STD``) give the comparison near ties."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    layers = sz["num_hidden_layers"]
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean = 0.0
        if name.endswith("wte/table"):
            std = EMBED_STD
        elif name.endswith(("scale", "q_norm", "kv_norm")):
            std, mean = NORM_STD, 1.0
        elif name.endswith(("moe/gate", "moe/up")):
            std = 1.0 / math.sqrt(shape[2])
        elif name.endswith("moe/down"):
            std = 1.0 / math.sqrt(shape[1] * 2 * layers)
        else:
            std = 1.0 / math.sqrt(shape[0])
            if name.endswith(("attn/out_kernel", "moe/shared_down")):
                std /= math.sqrt(2 * layers)
        how.append((shape, std, mean, name.endswith("moe/router")))

    def build(key):
        out = []
        for i, (shape, std, mean, router) in enumerate(how):
            # a leaf at a time: a layer's experts drawn together would be
            # 3 GB of float32 before the cast
            x = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if router:
                x *= ROUTER_COLUMN_NORM / jnp.linalg.norm(x, axis=0)
            out.append(x.astype(jnp.bfloat16))
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


def yarn_inv_freq(dim, rope):
    """The ``dim / 2`` rotary frequencies (float64 numpy -> float32)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = int(rope["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rope["beta_slow"]))), dim - 1)
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rotary(x, positions, inv_freq):
    """x (..., S, d), positions (S,): pairs (2i, 2i + 1) turned by
    ``positions * inv_freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(sz):
    rope = sz["rope"]
    m = 0.1 * float(rope.get("mscale_all_dim", 0)) \
        * math.log(float(rope["factor"])) + 1.0
    return (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5 * m * m


def attention(p, n, sz, quant=None):
    """n (S, D) normed input -> (S, H * v_head_dim): the expanded form, a
    group of ``H_GROUP`` heads at a time (every head's keys and values for
    26 k positions at once would be 0.9 GB a layer beside the weights)."""
    s = n.shape[0]
    h, eps = sz["num_attention_heads"], sz["rms_norm_eps"]
    nope, rd, vd = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                    sz["v_head_dim"])
    kr, rope = sz["kv_lora_rank"], sz["rope"]
    pos = jnp.arange(s)
    inv = yarn_inv_freq(rd, rope)
    beta = float(rope.get("llama_4_scaling_beta", 0))
    original = int(rope["original_max_position_embeddings"])
    by_pos = 1.0 + beta * jnp.log1p((pos // original).astype(jnp.float32))
    c_q = rms_norm(_matmul(n, p["q_a_kernel"], quant), p["q_norm"], eps)
    kv = _matmul(n, p["kv_a_kernel"], quant)
    c_kv = _round(rms_norm(kv[:, :kr], p["kv_norm"], eps), -1, quant)
    k_rope = _round(rotary(kv[:, kr:], pos, inv), -1, quant)    # (S, rd)
    scale = softmax_scale(sz)
    qb, hg = min(Q_BLOCK, s), math.gcd(H_GROUP, h)
    w_q = p["q_b_kernel"].reshape(-1, h // hg, hg * (nope + rd))
    w_kv = p["kv_b_kernel"].reshape(kr, h // hg, hg * (nope + vd))

    def heads(i):
        q = _matmul(c_q, w_q[:, i], quant).reshape(s, hg, nope + rd)
        q = q.transpose(1, 0, 2)                                # (hg, S, .)
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], pos, inv)], -1)
        q = _round(q * by_pos[None, :, None], -1, quant)
        kvb = _matmul(c_kv, w_kv[:, i], quant).reshape(s, hg, nope + vd)
        kvb = kvb.transpose(1, 0, 2)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_rope[None], (hg, s, rd))],
            -1)
        v = kvb[..., nope:]

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


def route(p, g, sz):
    """g (S, D) -> (S, experts) float32: each token's weight on each of ALL
    the experts the router chooses among, zero off its top-k."""
    logits = jnp.matmul(g, p["router"].astype(jnp.float32),
                        precision=HIGHEST)
    w, ids = jax.lax.top_k(jax.nn.softmax(logits, -1),
                           sz["num_experts_per_tok"])
    w = w / jnp.sum(w, -1, keepdims=True) * sz["routed_scaling_factor"]
    return jnp.zeros_like(logits).at[
        jnp.arange(g.shape[0])[:, None], ids].set(w)


def experts(p, g, sz, quant=None, which=None):
    """What the experts ``which`` (default: the held ones, ``0 .. held - 1``,
    leaf index = expert id) add for g (S, D), one expert at a time (each
    converted to float32 alone), plus nothing shared."""
    weights = route(p, g, sz)
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


def shared(p, g, quant=None):
    hid = jax.nn.silu(_matmul(g, p["shared_gate"], quant)) \
        * _matmul(g, p["shared_up"], quant)
    return _matmul(hid, p["shared_down"], quant)


def block(p, x, sz, quant=None):
    """One decoder layer on x (S, D) float32."""
    eps = sz["rms_norm_eps"]
    n = rms_norm(x, p["ln1"]["scale"], eps)
    x = x + _matmul(attention(p["attn"], n, sz, quant),
                    p["attn"]["out_kernel"], quant)
    g = rms_norm(x, p["ln2"]["scale"], eps)
    y = experts(p["moe"], g, sz, quant)
    if sz["n_shared_experts"]:
        y = y + shared(p["moe"], g, quant)
    return x + y


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
        self._embed = jax.jit(
            lambda p, ids: p["wte"]["table"][ids].astype(jnp.float32))
        self._block = jax.jit(functools.partial(block, sz=sz, quant=quant))
        self._head = jax.jit(functools.partial(head, sz=sz, quant=quant))

    def rows(self, ids, positions):
        """Logits (len(positions), V) predicting token p + 1 for each p."""
        buf = np.zeros((self.length,), np.int32)
        buf[:len(ids)] = ids
        x = self._embed(self.params, jnp.asarray(buf))
        for i in range(self.sz["num_hidden_layers"]):
            x = self._block(self.params[f"h{i}"], x)
        # fixed shape: pad the positions to a step's multiple, cut after
        pos = np.zeros((-(-len(positions) // LENGTH_STEP) * LENGTH_STEP,),
                       np.int32)
        pos[:len(positions)] = positions
        out = self._head(self.params, x, jnp.asarray(pos))
        return np.asarray(out[:len(positions)])
