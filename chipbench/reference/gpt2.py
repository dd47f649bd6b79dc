"""GPT-2 as published (Radford et al. 2019; openai-community/gpt2 config.json),
written out in plain ``jax.numpy``: float32, matmul precision "highest", no
cache, no kernels, no batching tricks. It imports nothing of the program.

    x   = wte[ids] + wpe[positions]
    x  += proj(attn(ln1(x)));  x += mlp(ln2(x))        (pre-LN, n_layer times)
    logits = ln_f(x) @ wte.T                            (tied head)

LayerNorm eps 1e-5, GELU in its tanh form (``gelu_new``), causal softmax
attention with scale 1/sqrt(head), MLP width 4 x n_embd.

The weights are made HERE from a seed (:func:`make_params`), in one jitted
call on the device; the benchmark hands the same tree to the program, so the
reference takes nothing the program has made. The tree's leaf names are the
interface the program's ``GPT2`` reads: ``wte.table``, ``wpe.pos``, ``ln_f``,
``h<i>.{ln1,ln2}.{scale,bias}``, ``h<i>.attn.{qkv_kernel,qkv_bias,out_kernel,
out_bias}`` (q, k, v as thirds of the fused kernel's columns, heads contiguous
inside each), ``h<i>.{fc,proj}.{kernel,bias}``.

``quant="int8"`` or ``"fp8"`` is the CONTROL of the benchmark's comparison,
never the yardstick: every matmul's two operands are rounded to int8 or to
float8 e4m3 (symmetric, one scale per row of the activation and per output
column of the weight), the precisions next below the bf16 the configurations
state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
INIT_STD = 0.02
# Every token of the vocabulary's upper half is the TWIN of one in the lower
# half: the same embedding row plus noise of this deviation. Served tokens are
# all the benchmark sees of the timed path, and a greedy token only moves
# where two logits all but tie; among ~50,000 independent logits that is one
# position in some hundreds, too few to tell bf16 from int8 in a run's few
# hundred tokens. Twins differ by ~sqrt(n_embd) x this in their logit (0.02
# at 1280 wide), a few times bf16's own error, so that a tenth of all
# positions sits on a near tie and the gap statistics are steady.
TWIN_STD = 5.6e-4
# ... and the position table is as large as the blocks' sum, not GPT-2's 0.01:
# greedy text of a random model falls into loops, in which one context, and
# so one and the same near tie or none, repeats for a whole request (PR 23's
# chip runs: a handful of independent contexts in 300 tokens). A position
# term of this size turns the hidden state at every step, so every served
# token is a draw of its own.
WPE_STD = 1.0


# The keys of the published config that are widths: ``reduced`` may name none
# of them (``spec.check_cut``). The family has no key for a head's size, which
# is n_embd / n_head, so the number of heads counts as one.
WIDTH_KEYS = ("n_embd", "n_inner", "n_head")
LENGTH_STEP = 128       # a Forward is built for a multiple of this


def sizes_of(cfg: dict) -> dict:
    """The sizes the reference needs, by the published config's key names,
    and the two the harness reads, under its own names: ``vocab_size`` (ids
    are drawn from and checked against it) and ``positions`` (the most a
    request may take, prompt and output together)."""
    return dict(n_layer=int(cfg["n_layer"]), n_embd=int(cfg["n_embd"]),
                n_head=int(cfg["n_head"]), vocab_size=int(cfg["vocab_size"]),
                n_positions=int(cfg["n_positions"]),
                positions=int(cfg["n_positions"]))


def check_program(model, sz: dict, name: str):
    """Refuse a program whose model is not the configuration's."""
    got = dict(n_layer=model.num_layers, n_embd=model.d_model,
               n_head=model.num_heads, vocab_size=model.vocab_size,
               n_positions=model.max_len)
    want = {k: sz[k] for k in got}
    if got != want:
        raise SystemExit(f"the program's {name} has sizes {got}, the "
                         f"configuration file says {want}")


def forward_length(sz: dict, longest: int) -> int:
    """The length to build a :class:`Forward` for, given the longest sequence
    it will be asked: the next multiple of ``LENGTH_STEP``, so that runs whose
    longest request differs by a few tokens share one compiled program."""
    return min(sz["n_positions"], -(-longest // LENGTH_STEP) * LENGTH_STEP)


def param_shapes(sz: dict) -> dict:
    d, v, p = sz["n_embd"], sz["vocab_size"], sz["n_positions"]
    tree = {"wte": {"table": (v, d)}, "wpe": {"pos": (p, d)},
            "ln_f": {"scale": (d,), "bias": (d,)}}
    for i in range(sz["n_layer"]):
        tree[f"h{i}"] = {
            "ln1": {"scale": (d,), "bias": (d,)},
            "attn": {"qkv_kernel": (d, 3 * d), "qkv_bias": (3 * d,),
                     "out_kernel": (d, d), "out_bias": (d,)},
            "ln2": {"scale": (d,), "bias": (d,)},
            "fc": {"kernel": (d, 4 * d), "bias": (4 * d,)},
            "proj": {"kernel": (4 * d, d), "bias": (d,)},
        }
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def make_params(sz: dict, seed: int):
    """The whole parameter tree from ``seed`` in ONE jitted call on the
    default device, in the type the program keeps it in (here float32
    masters: ``tnn_tpu.models.create`` computes in bf16 over them): normal,
    mean 0, with

      wte 0.02 (GPT-2's own), wpe ``WPE_STD``
      every matmul kernel 1/sqrt(fan_in), and the two residual projections
        a further 1/sqrt(2 n_layer)    (GPT-2's residual scaling)
      biases 0.02, LayerNorm scales 1 + 0.02 n, LayerNorm biases 0.02

    GPT-2's own 0.02 on the kernels would leave a random model's residual
    stream to the token's own embedding: through the tied head every position
    then predicts its own input by eight deviations, and no arithmetic error
    could move a greedy token. Kernels that keep the variance make the logits
    a function of the whole context (spread ~0.7, near ties every few dozen
    positions), which is what lets the comparison tell bf16 from int8.
    Perturbed biases and LayerNorm parameters let it see a dropped one, and
    the embedding table's twin rows (``TWIN_STD``) give it near ties to see.
    Leaves of one shape and scale are drawn together (one generator call per
    kind of leaf, not one per leaf: the program stays small)."""
    shapes = param_shapes(sz)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    kinds = {}          # (shape, std, offset) -> positions among the leaves
    for i, (path, shape) in enumerate(leaves):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        std = INIT_STD
        if name.endswith("kernel"):
            std = 1.0 / math.sqrt(shape[0])
            if name.endswith(("attn/out_kernel", "proj/kernel")):
                std /= math.sqrt(2 * sz["n_layer"])
        elif name.endswith("wpe/pos"):
            std = WPE_STD
        offset = 1.0 if name.endswith("scale") else 0.0
        kinds.setdefault((shape, std, offset), []).append(i)

    def build(key):
        out = [None] * len(leaves)
        for k, ((shape, std, offset), where) in enumerate(kinds.items()):
            x = offset + std * jax.random.normal(
                jax.random.fold_in(key, k), (len(where),) + shape,
                jnp.float32)
            for j, i in enumerate(where):
                out[i] = x[j]
        tree = jax.tree_util.tree_unflatten(treedef, out)
        table = tree["wte"]["table"]
        half = table.shape[0] // 2
        tree["wte"]["table"] = table.at[half:2 * half].set(
            table[:half] + TWIN_STD * jax.random.normal(
                jax.random.fold_in(key, len(kinds)), (half, table.shape[1]),
                jnp.float32))
        return tree

    # PRNGKey folds a seed of more than 32 bits itself
    return jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 63)))


# ------------------------------------------------------------- forward ----

def _fake_int8(x, axis):
    """Round to int8 with one symmetric scale along ``axis``; gradients pass
    straight through (the control trains through its own rounding)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x, axis):
    """Round to float8 (e4m3: three bits of mantissa), the largest magnitude
    along ``axis`` scaled to the format's 448; gradients pass through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


_ROUND = {"int8": _fake_int8, "fp8": _fake_fp8}


def _round(x, axis, quant):
    if quant is None:
        return x
    if quant not in _ROUND:
        raise ValueError(f"unknown control precision {quant!r}")
    return _ROUND[quant](x, axis)


def _matmul(x, w, quant):
    x = _round(x, -1, quant)            # one scale per activation row
    w = _round(w, 0, quant)             # one per output column
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, n_head, quant=None):
    """One pre-LN decoder block on x (S, D)."""
    s, d = x.shape
    dh = d // n_head
    h = _layer_norm(x, p["ln1"])
    qkv = _matmul(h, p["attn"]["qkv_kernel"], quant) + p["attn"]["qkv_bias"]
    q, k, v = (t.reshape(s, n_head, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    q, k, v = (_round(t, -1, quant) for t in (q, k, v))
    scores = jnp.einsum("hqd,hkd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqk,hkd->hqd", att, v,
                     precision=jax.lax.Precision.HIGHEST)
    ctx = ctx.transpose(1, 0, 2).reshape(s, d)
    x = x + _matmul(ctx, p["attn"]["out_kernel"], quant) + p["attn"]["out_bias"]
    h = _layer_norm(x, p["ln2"])
    h = _gelu_new(_matmul(h, p["fc"]["kernel"], quant) + p["fc"]["bias"])
    return x + _matmul(h, p["proj"]["kernel"], quant) + p["proj"]["bias"]


def hidden(params, ids, sz, quant=None):
    """ids (S,) int32 -> final hidden (S, D), layer after layer."""
    x = params["wte"]["table"][ids] + params["wpe"]["pos"][:ids.shape[0]]
    for i in range(sz["n_layer"]):
        x = block(params[f"h{i}"], x, sz["n_head"], quant)
    return _layer_norm(x, params["ln_f"])


def logits(params, ids, sz, quant=None):
    """ids (S,) -> logits (S, V) f32: row t predicts token t + 1."""
    return _matmul(hidden(params, ids, sz, quant),
                   params["wte"]["table"].T, quant)


def loss(params, ids, labels, sz, quant=None):
    """Mean next-token cross entropy of ONE sequence: ids (S,), labels (S,)
    (the token that follows each position). Returns (sum of nll, count) so
    that a caller working row by row can form the batch mean exactly."""
    lg = logits(params, ids, sz, quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll)


class Forward:
    """Jitted, layer-by-layer logits of one sequence at a time, padded to one
    fixed length (causal: padding never reaches an earlier position), so one
    small program serves every request of a run."""

    def __init__(self, params, sz, length, quant=None):
        self.params, self.sz, self.length, self.quant = params, sz, length, quant
        self._embed = jax.jit(
            lambda p, ids: p["wte"]["table"][ids] + p["wpe"]["pos"][:length])
        self._block = jax.jit(functools.partial(
            block, n_head=sz["n_head"], quant=quant))
        self._head = jax.jit(lambda p, x, pos: _matmul(
            _layer_norm(x, p["ln_f"])[pos], p["wte"]["table"].T, quant))

    def rows(self, ids, positions):
        """Logits (len(positions), V) predicting token p + 1 for each p."""
        import numpy as np

        buf = np.zeros((self.length,), np.int32)
        buf[:len(ids)] = ids
        x = self._embed(self.params, jnp.asarray(buf))
        for i in range(self.sz["n_layer"]):
            x = self._block(self.params[f"h{i}"], x)
        pos = np.zeros((self.length,), np.int32)     # fixed shape: pad, cut
        pos[:len(positions)] = positions
        out = self._head(self.params, x, jnp.asarray(pos))
        return np.asarray(out[:len(positions)])
