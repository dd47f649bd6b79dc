"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Beyond the reference: TNN has no MoE or expert parallelism of any kind. On TPU
the canonical design (Mesh-TensorFlow/Switch/GShard lineage) is einsum
dispatch/combine over an expert-stacked parameter tree: all experts' weights
carry a leading E dim sharded over the "expert" mesh axis, and GSPMD lowers
the dispatch/combine einsums into all-to-alls over ICI — no hand-written
routing communication.

Routing is top-k softmax gating with per-expert capacity; tokens over capacity
fall through (their combine weight is zero) — the standard capacity trick that
keeps every tensor static-shaped for XLA. The Switch-style load-balancing
auxiliary loss travels through the layer's mutable state under "aux_loss";
``make_train_step`` sums every such leaf into the training loss
(train/step.py:aux_loss_sum), so MoE models get load balancing through the
normal training path — and the compiled pipeline collects each stage's
aux_loss leaves per active microbatch into its loss accumulator
(parallel/pipeline.py), so an MoE stage inside a pipeline trains balanced
too (round-4; verified against single-device grad accumulation in
tests/test_parallel.py).
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.module import Module, register_module
from . import activations as act_lib
from . import initializers


@register_module("moe")
class MoE(Module):
    """Top-k routed expert FFN over (N, S, D) activations.

    ``hidden`` defaults to 4*D (the transformer FFN convention). With
    num_experts=1, top_k=1 and enough capacity this is exactly a Dense->act->
    Dense block — the equivalence is tested.
    """

    def __init__(self, num_experts: int, hidden: Optional[int] = None,
                 top_k: int = 2, capacity_factor: float = 2.0,
                 activation: str = "gelu", aux_weight: float = 0.01,
                 hidden_ratio: int = 4, dispatch: str = "einsum",
                 name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_experts = int(num_experts)
        self.hidden = hidden if hidden is None else int(hidden)
        self.hidden_ratio = int(hidden_ratio)  # used when hidden is None
        self.top_k = int(top_k)
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {top_k} not in [1, {num_experts}]")
        self.capacity_factor = float(capacity_factor)
        self.activation = activation
        self.aux_weight = float(aux_weight)
        if dispatch not in ("einsum", "sort"):
            raise ValueError(f"dispatch {dispatch!r} not in (einsum, sort)")
        # "einsum": GShard/Switch-style (T, E, C) one-hot dispatch/combine —
        #   GSPMD lowers it to all-to-alls over the expert mesh axis; the
        #   multi-chip path. "sort": argsort tokens by expert and gather into
        #   the (E, C, D) buffers directly — no (T, E, C) tensor ever exists
        #   (that tensor is THE memory hog at scale: T=8192 E=64 C=256 makes
        #   it 537 MB even in bf16). Single-device/memory-optimized path.
        self.dispatch = dispatch

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        h = self.hidden or self.hidden_ratio * d
        e = self.num_experts
        kg, ki, ko = jax.random.split(rng, 3)
        pd = self.policy.param_dtype
        init = initializers.get("xavier_uniform")
        params = {
            "gate": {"kernel": init(kg, (d, e), pd)},
            "w_in": init(ki, (e, d, h), pd),
            "b_in": jnp.zeros((e, h), pd),
            "w_out": init(ko, (e, h, d), pd),
            "b_out": jnp.zeros((e, d), pd),
        }
        # state structure must match _apply's exactly — a {} here would crash
        # lax.scan carries (grad accumulation) on the first step
        return params, {"aux_loss": jnp.zeros((), jnp.float32)}

    def _dispatch_einsum(self, xt, top_e, top_p, t, e, cap, compute):
        """GShard/Switch (T, E, C) one-hot dispatch — the GSPMD/multi-chip
        path (all-to-alls are inserted from the einsums). Returns the (E, C,
        D) expert inputs and a combine(ye) closure."""
        k = self.top_k
        # per-expert positions via cumsum over (k-slot, token) order; tokens
        # beyond an expert's capacity get weight zero (static shapes for XLA)
        onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)      # (T, k, E)
        flat = onehot.transpose(1, 0, 2).reshape(k * t, e)
        pos = jnp.cumsum(flat, axis=0) - flat                     # (k*T, E)
        pos = pos.reshape(k, t, e).transpose(1, 0, 2)             # (T, k, E)
        pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)    # (T, k)
        in_cap = pos < cap
        weight = top_p * in_cap                                   # (T, k)

        # dispatch/combine tensors (T, E, C). dispatch holds exact 0/1 values,
        # so it is built directly in the compute dtype — the (T, E, C) pair
        # dominates MoE memory (bf16 halves the bigger one; combine stays
        # f32: its routing weights need the precision). dispatch="sort"
        # avoids these tensors entirely on one device.
        pos_oh = jax.nn.one_hot(jnp.where(in_cap, pos, cap), cap + 1,
                                dtype=jnp.float32)[..., :cap]     # (T, k, C)
        dispatch = jnp.einsum("tke,tkc->tec",
                              (onehot * in_cap[..., None]).astype(compute),
                              pos_oh.astype(compute))
        combine = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh, weight)
        xe = jnp.einsum("tec,td->ecd", dispatch,
                        xt.astype(compute))                       # (E, C, D)

        def combine_fn(ye):
            return jnp.einsum("tec,ecd->td", combine,
                              ye.astype(jnp.float32))
        return xe, combine_fn

    def _dispatch_sort(self, xt, top_e, top_p, t, e, cap, compute):
        """Sort-based dispatch: argsort (token, k-slot) assignments by expert,
        rank each within its expert, scatter into the (E, C, D) buffer.
        Peak extra memory is O(T*k*D) + O(E*C*D) — the O(T*E*C) one-hot
        tensors never exist. Same capacity-drop semantics as the einsum path
        up to WHICH tokens drop when an expert overflows (einsum drops by
        token order, sort by sorted order); with no overflow they agree
        exactly (tested)."""
        k = self.top_k
        d = xt.shape[-1]
        e_flat = top_e.reshape(-1)                                # (T*k,)
        w_flat = top_p.reshape(-1)
        tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)       # (T*k,)
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = e_flat[order]
        # rank within expert = index - first index of that expert id
        start = jnp.searchsorted(e_sorted, e_sorted, side="left")
        rank = jnp.arange(t * k, dtype=jnp.int32) - start.astype(jnp.int32)
        valid = rank < cap
        slot = jnp.where(valid, e_sorted * cap + rank, e * cap)   # drop slot
        xe_flat = (jnp.zeros((e * cap, d), compute)
                   .at[slot].set(xt[tok[order]].astype(compute), mode="drop"))
        xe = xe_flat.reshape(e, cap, d)

        def combine_fn(ye):
            back = ye.reshape(e * cap, -1).astype(jnp.float32)
            # mode="fill" handles the out-of-range drop slot; the weight
            # multiply (zero for dropped assignments) is the single mask that
            # enforces capacity semantics
            rows = back.at[slot, :].get(mode="fill", fill_value=0.0)
            rows = rows * (w_flat[order] * valid)[:, None]        # (T*k, D)
            return (jnp.zeros((t, rows.shape[-1]), jnp.float32)
                    .at[tok[order]].add(rows))
        return xe, combine_fn

    def _capacity(self, tokens: int) -> int:
        cap = math.ceil(self.top_k * tokens / self.num_experts
                        * self.capacity_factor)
        return max(1, min(int(cap), tokens))

    def _apply(self, params, state, x, *, train, rng):
        n, s, d = x.shape
        t = n * s
        e = self.num_experts
        cap = self._capacity(t)
        compute = self.policy.compute_dtype
        xt = x.reshape(t, d)

        # -- routing (f32 for a stable softmax) -------------------------------
        gate_w = self.policy.cast_param(params["gate"]["kernel"])
        logits = jax.lax.dot_general(
            xt, gate_w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, self.top_k)   # (T, k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)  # renormalize

        if self.dispatch == "sort":
            xe, combine_fn = self._dispatch_sort(xt, top_e, top_p, t, e, cap,
                                                 compute)
        else:
            xe, combine_fn = self._dispatch_einsum(xt, top_e, top_p, t, e,
                                                   cap, compute)

        # -- expert computation (batched over the expert dim; the leading E of
        # every parameter shards over the "expert" mesh axis) -----------------
        w_in = self.policy.cast_param(params["w_in"])
        w_out = self.policy.cast_param(params["w_out"])
        hmid = jnp.einsum("ecd,edh->ech", xe, w_in,
                          preferred_element_type=jnp.float32)
        hmid = hmid + self.policy.cast_param(params["b_in"])[:, None, :]
        hmid = act_lib.get(self.activation)(hmid).astype(compute)
        ye = jnp.einsum("ech,ehd->ecd", hmid, w_out,
                        preferred_element_type=jnp.float32)
        ye = ye + self.policy.cast_param(params["b_out"])[:, None, :]

        out = combine_fn(ye)
        out = out.astype(x.dtype).reshape(n, s, d)

        # Switch-style load-balance aux loss: E * sum_e fraction_e * prob_e
        # (expert counts via scatter-add — no (T, k, E) one-hot needed)
        frac_e = (jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
                  / (t * self.top_k))                                # (E,)
        prob_e = jnp.mean(probs, axis=0)                             # (E,)
        aux = self.aux_weight * e * jnp.sum(frac_e * prob_e)
        return out, {"aux_loss": aux}

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        return {"num_experts": self.num_experts, "hidden": self.hidden,
                "top_k": self.top_k, "capacity_factor": self.capacity_factor,
                "activation": self.activation, "aux_weight": self.aux_weight,
                "hidden_ratio": self.hidden_ratio, "dispatch": self.dispatch}


def ep_rules(axis: str = "expert"):
    """Path rules for expert-stacked MoE params (w_in/b_in/w_out/b_out carry a
    leading E dim; the gate replicates). Path-based, not shape-based — a gate
    kernel whose input dim happens to equal E must not get expert-sharded."""
    from jax.sharding import PartitionSpec as P

    return [(r".*(^|/)(w_in|b_in|w_out|b_out)$", P(axis))]


def shard_params_ep(params, mesh, axis: str = "expert"):
    """Place expert-stacked leaves over the expert axis; everything else
    replicates. GSPMD then inserts the dispatch/combine all-to-alls."""
    from ..parallel.tensor_parallel import shard_params_tp

    return shard_params_tp(params, mesh, rules=ep_rules(axis))


# -- one chip's share of a dropless expert layer (serving) ------------------

_COUNTS = threading.local()
# the most bytes ``ExpertShare``'s sorted buffer takes before a step's tokens
# go through the held experts in slices (64 MiB: 8,192 assignments of 4,096
# values in bfloat16)
SORTED_BYTES = 64 << 20


class _Counts(list):
    """What ``collect_counts`` yields: the layers' ``(held,)`` counts, with
    the ``(2,)`` counters of the layers that have zero-compute experts
    (``ExpertShare._identity``) in a list of their own, ``zero``."""

    def __init__(self):
        super().__init__()
        self.zero = []


@contextlib.contextmanager
def collect_counts():
    """Inside this context every ``ExpertShare`` applied (traced) appends its
    ``(held,)`` count of assignments, in layer order, to the list it yields:
    how a serving step program returns its expert counters beside its tokens
    without the model's ``apply_paged`` changing its signature. A layer with
    zero-compute experts appends its two counters of them to the list's
    ``zero``."""
    prev = getattr(_COUNTS, "sink", None)
    _COUNTS.sink = sink = _Counts()
    try:
        yield sink
    finally:
        _COUNTS.sink = prev


@register_module("expert_share")
class ExpertShare(Module):
    """The experts ONE chip holds of a routed layer of ``num_experts``,
    beside ``shared`` shared experts (one gated feed-forward of ``shared *
    hidden``, added once): the share of an expert-parallel deployment that
    divides each layer over several chips.

    The router is whole: float32 logits over all ``num_experts`` (and the
    ``zero_experts`` behind them), then ``score`` "softmax": the ``top_k``
    largest probabilities renormalised to sum 1; or "sigmoid": scores ``p =
    sigmoid(logits)``, the ``top_k`` largest of ``p + expert_bias`` (a leaf
    that only SELECTS: the balancing bias), weighted by ``p`` alone,
    renormalised; or "softmax_raw": ``p = softmax(logits)``, selected by ``p
    + expert_bias`` likewise, weighted by ``p`` as it is, NOT renormalised.
    Each times ``route_scale``. Of a step's assignments
    those that fall on a HELD expert are sorted by expert, each expert's
    group padded to the kernel's row tile, and ``ops.pallas.expert_gmm``
    walks the groups: an expert with no token is never fetched, and no token
    is dropped at any imbalance (the sorted buffer holds every assignment
    and a tile of padding an expert). What absent experts would add is left
    out; nothing here stands in for the other chips or their exchange.

    ``zero_experts``: ids ``num_experts ..`` of the router are zero-compute
    IDENTITY experts. They have no weights and live on no chip: a token's
    picks among them add the sum of their weights times the layer's input,
    computed here for this chip's own tokens, never sorted into the grouped
    product and never counted as held or absent.

    Leaves: ``router`` (D, num_experts + zero_experts); unless the score is
    "softmax", ``expert_bias`` (the router's width,) float32; ``gate``,
    ``up``, ``down``
    (held, hidden, D): "out x in" for gate and up, "in x out" for down, so a
    block of hidden units is contiguous rows in each; the shared expert's
    ``shared_gate`` / ``shared_up`` (D, shared * hidden) and ``shared_down``
    (shared * hidden, D); with the option ``shared_gated``, ``shared_router``
    (D, 1): the shared expert's output times ``sigmoid(x . shared_router)``."""

    def __init__(self, num_experts: int, held, top_k: int, hidden: int,
                 shared: int = 0, score: str = "softmax",
                 route_scale: float = 1.0, zero_experts: int = 0,
                 shared_gated: bool = False, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        # the shared expert's output times sigmoid(x . w_sg), a token's own
        # gate (leaf ``shared_router`` (D, 1))
        self.shared_gated = bool(shared_gated)
        if self.shared_gated and not shared:
            raise ValueError("a gate on the shared expert needs one")
        if score not in ("softmax", "sigmoid", "softmax_raw"):
            raise ValueError(f"score {score!r}: softmax, sigmoid or "
                             "softmax_raw")
        self.score, self.route_scale = score, float(route_scale)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.zero_experts = int(zero_experts)
        self.width = self.num_experts + self.zero_experts   # the router's
        self.held = tuple(int(e) for e in held)
        self.hidden, self.shared = int(hidden), int(shared)
        if not self.held or len(set(self.held)) != len(self.held) or not all(
                0 <= e < self.num_experts for e in self.held):
            raise ValueError(f"held experts {self.held} are distinct ids "
                             f"below {self.num_experts}")
        if not 1 <= self.top_k <= self.width:
            raise ValueError(f"top_k {top_k} not in [1, {self.width}]")
        slot = np.full((self.width,), -1, np.int32)
        slot[list(self.held)] = np.arange(len(self.held))
        self._slot = slot           # expert id -> held slot, -1: elsewhere

    def _init(self, rng, input_shape):
        d, f, n = input_shape[-1], self.hidden, len(self.held)
        pd = self.policy.param_dtype
        ks = jax.random.split(rng, 8 if self.shared_gated else 7)

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(pd)

        params = {"router": normal(ks[0], (d, self.width), d),
                  "gate": normal(ks[1], (n, f, d), d),
                  "up": normal(ks[2], (n, f, d), d),
                  "down": normal(ks[3], (n, f, d), f)}
        if self.score != "softmax":
            params["expert_bias"] = jnp.zeros((self.width,), jnp.float32)
        if self.shared:
            fs = self.shared * f
            params.update(shared_gate=normal(ks[4], (d, fs), d),
                          shared_up=normal(ks[5], (d, fs), d),
                          shared_down=normal(ks[6], (fs, d), fs))
        if self.shared_gated:
            params["shared_router"] = normal(ks[7], (d, 1), d)
        return params, {}

    @jax.named_scope("moe_route")
    def route(self, params, x):
        """x (T, D) -> (ids (T, k) int32, weights (T, k) float32): the
        ``top_k`` of float32 scores over ALL the router's ids (the class
        says how each ``score`` selects, weighs and normalises)."""
        logits = jnp.matmul(x.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if self.score == "softmax":
            w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   self.top_k)
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        else:
            p = jax.nn.sigmoid(logits) if self.score == "sigmoid" \
                else jax.nn.softmax(logits, axis=-1)
            _, ids = jax.lax.top_k(
                p + params["expert_bias"].astype(jnp.float32), self.top_k)
            w = jnp.take_along_axis(p, ids, axis=-1)
            if self.score == "sigmoid":
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if self.route_scale != 1.0:
            w = w * self.route_scale
        return ids.astype(jnp.int32), w

    @jax.named_scope("moe_route")
    def _sort(self, ids, live, tile):
        """Lay the held assignments out by expert. ids (T, k), live (T,) ->
        ``src`` (M,) the token of each row of the sorted buffer (-1: padding),
        ``dest`` (T, k) the row of each assignment (-1: not held, or a dead
        token), ``tile_expert`` (M / tile,), ``live_tiles``, ``counts``
        (held,)."""
        t, k = ids.shape
        n = len(self.held)
        a = t * k
        # room for every assignment and a tile's padding an expert
        tiles = -(-a // tile) + n
        slot = jnp.asarray(self._slot)[ids]
        key = jnp.where((slot >= 0) & live[:, None], slot, n).reshape(a)
        order = jnp.argsort(key, stable=True)
        skey = key[order]
        counts = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                         dtype=jnp.int32)
        first = jnp.cumsum(counts) - counts             # group starts, sorted
        padded = -(-counts // tile) * tile
        start = jnp.cumsum(padded) - padded             # group starts, padded
        held = skey < n
        e = jnp.minimum(skey, n - 1)
        row = jnp.where(held, start[e] + jnp.arange(a) - first[e],
                        tiles * tile)                   # past the end: dropped
        src = jnp.full((tiles * tile,), -1, jnp.int32).at[row].set(
            (order // k).astype(jnp.int32), mode="drop")
        dest = jnp.full((a,), -1, jnp.int32).at[order].set(
            jnp.where(held, row, -1).astype(jnp.int32)).reshape(t, k)
        ends = jnp.cumsum(padded)
        live_tiles = ends[-1] // tile
        at = jnp.minimum(jnp.arange(tiles), jnp.maximum(live_tiles - 1, 0))
        tile_expert = jnp.minimum(
            jnp.searchsorted(ends, at * tile, side="right"), n - 1)
        return src, dest, tile_expert.astype(jnp.int32), live_tiles, counts

    def routed(self, params, x, live=None):
        """What the HELD experts (and the zero-compute ones) add for x (T,
        D): (y (T, D) float32, counts (held,) int32 of the assignments each
        held expert took). ``live`` (T,) bool: a dead token takes no expert
        and counts nowhere.

        The sorted buffer has room for every assignment, so a step whose
        buffer would pass ``SORTED_BYTES`` (a prompt step of a wide model
        with many picks a token) sorts and multiplies its tokens a slice at
        a time: the same sums, the held weights read once a slice."""
        t, d = x.shape
        if live is None:
            live = jnp.ones((t,), bool)
        ids, w = self.route(params, x)
        row = self.top_k * d * jnp.dtype(self.policy.compute_dtype).itemsize
        fit = max(1, SORTED_BYTES // row)   # tokens whose picks the bound holds
        parts = next(p for p in range(1, t + 1)
                     if t % p == 0 and t // p <= fit)
        if parts == 1:
            y, counts = self._held(params, x, ids, w, live)
        else:
            y, counts = jax.lax.map(
                lambda part: self._held(params, *part),
                tuple(v.reshape((parts, t // parts) + v.shape[1:])
                      for v in (x, ids, w, live)))
            y, counts = y.reshape(t, d), jnp.sum(counts, axis=0)
        if self.zero_experts:
            y = self._identity(y, x, ids, w, live)
        return y, counts

    def _held(self, params, x, ids, w, live):
        """The held experts' weighted sum for routed tokens x (T, D), and
        their counts."""
        from ..ops.pallas.expert_gmm import expert_gmm, row_tile

        tile = row_tile(x.shape[0] * self.top_k)
        src, dest, tile_expert, live_tiles, counts = self._sort(
            ids, live, tile)
        cast = self.policy.cast_param
        with jax.named_scope("moe_experts"):
            xc = self.policy.cast_in(x)
            rows = jnp.where((src >= 0)[:, None], xc[jnp.maximum(src, 0)], 0)
            ys = expert_gmm(rows, cast(params["gate"]), cast(params["up"]),
                            cast(params["down"]), tile_expert, live_tiles,
                            tile=tile)
            got = jnp.where((dest >= 0)[..., None],
                            ys[jnp.maximum(dest, 0)].astype(jnp.float32), 0.0)
            y = jnp.sum(got * w[..., None], axis=1)
        return y, counts

    @jax.named_scope("moe_zero")
    def _identity(self, y, x, ids, w, live):
        """``y`` plus what a token's picks among the zero-compute experts
        add (their weights' sum times the token's own row). Inside
        ``collect_counts`` the layer's two counters of them go to the list's
        ``zero``: the live tokens' picks that fell on them, and the most
        REAL experts (held here or elsewhere) any live token picked."""
        zero = (ids >= self.num_experts) & live[:, None]
        y = y + jnp.sum(jnp.where(zero, w, 0.0), axis=1)[:, None] \
            * x.astype(jnp.float32)
        sink = getattr(_COUNTS, "sink", None)
        if sink is not None:
            real = jnp.where(live, self.top_k - jnp.sum(zero, axis=1), 0)
            sink.zero.append(
                jnp.stack([jnp.sum(zero), jnp.max(real)]).astype(jnp.int32))
        return y

    @jax.named_scope("moe_shared")
    def shared_out(self, params, x):
        from ..ops.pallas.quant_matmul import qmatmul

        cast = self.policy.cast_param
        xc = self.policy.cast_in(x)
        g = qmatmul(xc, cast(params["shared_gate"]))
        u = qmatmul(xc, cast(params["shared_up"])).astype(xc.dtype)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xc.dtype) * u
        y = qmatmul(h, cast(params["shared_down"]))
        if self.shared_gated:
            gate = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32),
                params["shared_router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            y = y.astype(jnp.float32) * gate
        return y

    def _apply(self, params, state, x, *, train, rng, live=None):
        """x (..., D) -> the held experts' share plus the shared expert, in
        x's dtype. Inside ``collect_counts`` the layer's counts go to its
        list."""
        lead, d = x.shape[:-1], x.shape[-1]
        flat = x.reshape(-1, d)
        y, counts = self.routed(
            params, flat, None if live is None else live.reshape(-1))
        if self.shared:
            y = y + self.shared_out(params, flat).astype(jnp.float32)
        sink = getattr(_COUNTS, "sink", None)
        if sink is not None:
            sink.append(counts)
        return y.astype(x.dtype).reshape(lead + (d,)), state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_experts": self.num_experts, "held": list(self.held),
               "top_k": self.top_k, "hidden": self.hidden,
               "shared": self.shared}
        if self.score != "softmax":
            cfg["score"] = self.score
        if self.route_scale != 1.0:
            cfg["route_scale"] = self.route_scale
        if self.zero_experts:
            cfg["zero_experts"] = self.zero_experts
        if self.shared_gated:
            cfg["shared_gated"] = True
        return cfg
