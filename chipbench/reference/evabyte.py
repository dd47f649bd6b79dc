"""EvaByte's decoder (https://huggingface.co/EvaByte/EvaByte, config.json:
``attention_class: "eva"``, ``chunk_size`` 16, ``window_size`` 2048) written
out in plain ``jax.numpy``: float32, matmul precision "highest", no cache, no
kernels, no pages. It imports nothing of the program.

The attention is EVA (Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542) in the form EvaByte's model code gives it, with
two learned vectors a head. There is no network in this sandbox: the equations
below are AS RECALLED, not as fetched. What ``config.json`` does not fix is
listed under ``assumed`` in the configuration file. A builder who knows the
source to differ corrects THIS file first; the program follows it.

One head, ``d`` = head size, ``W`` = ``window_size``, ``C`` = ``chunk_size``,
positions from 0. With ``x_t`` the normed input,

    q_t = R_t W_q x_t      k_t = R_t W_k x_t      v_t = W_v x_t

(``R_t``: rotary at position ``t``, theta ``rope_theta``, over all ``d`` dims,
halves paired as in the Llama family). Every head has two learned vectors
``phi``, ``mu`` in R^d. For chunk ``c`` (tokens ``Cc .. Cc + C - 1``):

    a_s  = softmax over s in chunk c of (k_s . phi)
    k~_c = sum_s a_s k_s + mu          v~_c = sum_s a_s v_s

For a query at ``t``, in window ``w = floor(t / W)``, with ``S_t = {wW .. t}``
(exact, causal) and ``P_t = {c : Cc + C - 1 < wW}`` (every chunk of every
EARLIER window; a chunk of the query's own window is never read as a summary):

    o_t = ( sum_{s in S_t} e^{q_t.k_s / sqrt(d)} v_s + sum_{c in P_t} e^{q_t.k~_c / sqrt(d)} v~_c )
        / ( sum_{s in S_t} e^{q_t.k_s / sqrt(d)}     + sum_{c in P_t} e^{q_t.k~_c / sqrt(d)}     )

one softmax over both kinds of key; then ``W_o``. For a sequence of at most
``W`` tokens this IS causal softmax attention.

    h = h + Attn(RMSNorm(h));   n = RMSNorm(h);   h = h + W_down(silu(W_gate n) * W_up n)

RMSNorm's gain is ``1 + g`` (``norm_add_unit_offset``), eps ``rms_norm_eps``;
no biases; the sum ``h`` stays float32 (``fp32_skip_add``). Output: final
RMSNorm, then the head ``hidden -> num_pred_heads x vocab``; the next byte's
logits are the first ``vocab`` columns, in float32 (``fp32_logits``). Heads
2..8 (multi-byte self-speculation) are held as parameters and not served.

The weights are made HERE from a seed (:func:`make_params`), bf16 leaves in one
jitted call, under the leaf names the program's model reads: ``wte.table``,
``ln_f.scale``, ``head.kernel``, ``h<i>.{ln1,ln2}.scale`` (the ``g`` above),
``h<i>.attn.{qkv_kernel, out_kernel, phi, mu}`` (q, k, v as thirds of the fused
kernel's columns, heads contiguous inside each; ``phi``/``mu`` (heads, d)),
``h<i>.{gate,up,down}.kernel``.

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands, the rows q, k, v and the
summaries are rounded (symmetric, one scale a row / an output column).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# The served logits' upper half are TWINS of the lower half: the same head
# column plus noise (see reference/gpt2.py: a greedy token only moves where
# two logits all but tie, and 320 independent logits tie too rarely to tell
# bf16 from fp8 in a run's tokens). This is the deviation of a twin's LOGIT
# from its sibling's: the noise of a column's entry is this over
# sqrt(hidden), three bf16 steps of a head column's entry at any width.
TWIN_STD = 0.0256
EMBED_STD = 1.0
NORM_STD = 0.02

# The keys of the published config that are widths: ``reduced`` names none.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "window_size", "chunk_size",
              "num_pred_heads", "num_attention_heads", "num_key_value_heads")
Q_BLOCK = 512           # attention runs over this many queries at a time


def sizes_of(cfg: dict) -> dict:
    """The sizes by the published config's key names, and the two the harness
    reads: ``vocab_size`` and ``positions`` (``served_positions``: what the
    engine is started for)."""
    sz = {k: int(cfg[k]) for k in (
        "num_hidden_layers", "hidden_size", "intermediate_size",
        "num_attention_heads", "vocab_size", "window_size", "chunk_size",
        "num_pred_heads", "max_position_embeddings")}
    sz["rope_theta"] = float(cfg["rope_theta"])
    sz["rms_norm_eps"] = float(cfg["rms_norm_eps"])
    sz["positions"] = int(cfg.get("served_positions",
                                  cfg["max_position_embeddings"]))
    sz["head_dim"] = sz["hidden_size"] // sz["num_attention_heads"]
    if sz["window_size"] % sz["chunk_size"]:
        raise ValueError("window_size is a multiple of chunk_size")
    return sz


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    got = dict(
        num_hidden_layers=model.num_layers, hidden_size=model.d_model,
        intermediate_size=model.mlp_hidden,
        num_attention_heads=model.num_heads, vocab_size=model.vocab_size,
        window_size=model.window, chunk_size=model.chunk,
        num_pred_heads=model.num_pred_heads,
        max_position_embeddings=model.max_len,
        rope_theta=float(model.rope_theta),
        rms_norm_eps=float(model.norm_eps))
    want = {k: sz[k] for k in got}
    if got != want or model.num_kv_heads != model.num_heads:
        raise SystemExit(f"the program's {name} has sizes {got}, the "
                         f"configuration file says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """Whole windows: runs whose longest request differs by less than a
    window share one compiled program."""
    w = sz["window_size"]
    return -(-longest // w) * w


def param_shapes(sz: dict) -> dict:
    d, f, v = sz["hidden_size"], sz["intermediate_size"], sz["vocab_size"]
    h, dh = sz["num_attention_heads"], sz["head_dim"]
    tree = {"wte": {"table": (v, d)}, "ln_f": {"scale": (d,)},
            "head": {"kernel": (d, sz["num_pred_heads"] * v)}}
    for i in range(sz["num_hidden_layers"]):
        tree[f"h{i}"] = {
            "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
            "attn": {"qkv_kernel": (d, 3 * d), "out_kernel": (d, d),
                     "phi": (h, dh), "mu": (h, dh)},
            "gate": {"kernel": (d, f)}, "up": {"kernel": (d, f)},
            "down": {"kernel": (f, d)}}
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole tree from ``seed`` in ONE jitted call on the default device,
    in bfloat16 (the type the program keeps these weights in: float32 masters
    of 16 layers would be 13 GB). Normal, mean 0:

      wte ``EMBED_STD``; every matmul kernel 1/sqrt(fan_in), the two residual
      projections (attn.out_kernel, down) a further 1/sqrt(2 layers);
      norm gains g ``NORM_STD`` (so a dropped unit offset or gain shows);
      phi, mu: normal clipped to +-1, times head_dim^-1/2 (assumed).

    Kernels that keep the variance make the logits a function of the whole
    context (see reference/gpt2.py); the head's twin columns (``TWIN_STD``)
    give the comparison near ties to see."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    layers = sz["num_hidden_layers"]
    how = []
    for path, shape in leaves:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        clip = False
        if name.endswith("kernel"):
            std = 1.0 / math.sqrt(shape[0])
            if name.endswith(("attn/out_kernel", "down/kernel")):
                std /= math.sqrt(2 * layers)
        elif name.endswith("wte/table"):
            std = EMBED_STD
        elif name.endswith(("phi", "mu")):
            std, clip = 1.0 / math.sqrt(sz["head_dim"]), True
        else:
            std = NORM_STD
        how.append((shape, std, clip))

    def build(key):
        out = []
        for i, (shape, std, clip) in enumerate(how):
            # a leaf at a time: sixteen layers' feed-forward kernels drawn
            # together would be 6 GB of float32 before the cast
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if clip:
                x = jnp.clip(x, -1.0, 1.0)
            out.append((std * x).astype(jnp.bfloat16))
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
    return x / jnp.sqrt(ms + eps) * (1.0 + g.astype(jnp.float32))


def rotary(x, positions, theta):
    """x (..., S, d), positions (S,): pairs (i, i + d/2) turned by
    ``positions * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def summaries(k, v, phi, mu, chunk):
    """k, v (H, S, d) -> (k~, v~) (H, S / chunk, d): the equations above."""
    h, s, d = k.shape
    kc = k.reshape(h, s // chunk, chunk, d)
    vc = v.reshape(h, s // chunk, chunk, d)
    a = jax.nn.softmax(jnp.einsum("hcsd,hd->hcs", kc, phi,
                                  precision=HIGHEST), axis=-1)
    ks = jnp.einsum("hcs,hcsd->hcd", a, kc, precision=HIGHEST) \
        + mu[:, None, :]
    vs = jnp.einsum("hcs,hcsd->hcd", a, vc, precision=HIGHEST)
    return ks, vs


def eva_attention(q, k, v, phi, mu, window, chunk, quant=None):
    """q, k (rotated), v: (H, S, d), S a multiple of ``window`` -> (H, S, d).
    Queries go ``Q_BLOCK`` at a time, so that 30 k positions fit."""
    h, s, d = q.shape
    ks, vs = summaries(k, v, phi.astype(jnp.float32),
                       mu.astype(jnp.float32), chunk)
    ks, vs = _round(ks, -1, quant), _round(vs, -1, quant)
    qb = min(Q_BLOCK, window)
    per = window // chunk

    def block(i):
        start = i * qb                           # first query of the block
        w0 = (start // window) * window          # its window's first token
        qi = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, w0, window, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, w0, window, axis=1)
        se = jnp.einsum("hqd,hkd->hqk", qi, kw, precision=HIGHEST)
        qpos = start + jnp.arange(qb)
        se = jnp.where((w0 + jnp.arange(window))[None, :] <= qpos[:, None],
                       se, -jnp.inf)
        ss = jnp.einsum("hqd,hcd->hqc", qi, ks, precision=HIGHEST)
        ss = jnp.where(jnp.arange(ks.shape[1])[None, :]
                       < (start // window) * per, ss, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([se, ss], -1) / math.sqrt(d), -1)
        return jnp.einsum("hqk,hkd->hqd", p[..., :window], vw,
                          precision=HIGHEST) \
            + jnp.einsum("hqc,hcd->hqd", p[..., window:], vs,
                         precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // qb))        # (S/qb, H, qb, d)
    return out.transpose(1, 0, 2, 3).reshape(h, s, d)


def block(p, x, sz, quant=None):
    """One decoder block on x (S, D) float32, S a multiple of the window."""
    s, d = x.shape
    h, dh, eps = sz["num_attention_heads"], sz["head_dim"], sz["rms_norm_eps"]
    n = rms_norm(x, p["ln1"]["scale"], eps)
    qkv = _matmul(n, p["attn"]["qkv_kernel"], quant)
    q, k, v = (t.reshape(s, h, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    pos = jnp.arange(s)
    q, k = rotary(q, pos, sz["rope_theta"]), rotary(k, pos, sz["rope_theta"])
    q, k, v = (_round(t, -1, quant) for t in (q, k, v))
    ctx = eva_attention(q, k, v, p["attn"]["phi"], p["attn"]["mu"],
                        sz["window_size"], sz["chunk_size"], quant)
    x = x + _matmul(ctx.transpose(1, 0, 2).reshape(s, d),
                    p["attn"]["out_kernel"], quant)
    rows = min(s, 4096)     # the feed-forward in row blocks: 11,008 wide

    def mlp(xr):
        n = rms_norm(xr, p["ln2"]["scale"], eps)
        g = _matmul(n, p["gate"]["kernel"], quant)
        u = _matmul(n, p["up"]["kernel"], quant)
        return xr + _matmul(jax.nn.silu(g) * u, p["down"]["kernel"], quant)

    if s % rows:
        return mlp(x)
    return jax.lax.map(mlp, x.reshape(s // rows, rows, d)).reshape(s, d)


def head(p, x, pos, sz, quant=None):
    """Next-byte logits (len(pos), vocab) f32: the first of the output heads."""
    n = rms_norm(x[pos], p["ln_f"]["scale"], sz["rms_norm_eps"])
    return _matmul(n, p["head"]["kernel"][:, :sz["vocab_size"]], quant)


class Forward:
    """Jitted, layer-by-layer logits of one sequence at a time, padded to one
    fixed length of whole windows (causal, and a chunk's summary is read only
    by later windows: padding never reaches an earlier position)."""

    def __init__(self, params, sz, length, quant=None):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        if length % sz["window_size"]:
            raise ValueError("a Forward is built for whole windows "
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
        # fixed shape: pad the positions to a window's multiple, cut after
        w = self.sz["window_size"]
        pos = np.zeros((-(-len(positions) // w) * w,), np.int32)
        pos[:len(positions)] = positions
        out = self._head(self.params, x, jnp.asarray(pos))
        return np.asarray(out[:len(positions)])
