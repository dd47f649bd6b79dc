"""Pipeline parallelism.

Parity-and-beyond with the reference's microbatch pipeline runtime
(docs/pipeline_architecture.md; Coordinator chain wiring coordinator.hpp:418-433; Worker
FORWARD_JOB/BACKWARD_JOB loop worker.hpp:145-193; Job{tensor, mb_id} job.hpp:93-129).

Three TPU-native implementations:

1. ``spmd_pipeline`` — homogeneous stages (stacked identical-structure params
   sharded over the "pipe" axis); GPipe fill/drain as a lax.scan inside shard_map
   with ppermute hops. jax.grad straight through it yields the backward pipeline.

2. ``HeteroPipeline`` / ``make_pipeline_train_step`` — the flagship path:
   ARBITRARY heterogeneous stages (shape-changing conv groups, different param
   structures) in ONE compiled SPMD program. Per-stage params/state are packed
   into padded f32 rows stacked over the pipe axis; activations hop as padded
   flat buffers over ICI; lax.switch on the stage index runs each device's own
   decode -> stage.apply -> encode. BatchNorm statistics update correctly under
   pipelining: each stage's packed net_state threads through the schedule scan
   and is committed only on ticks where that stage processed a real microbatch,
   reproducing the per-microbatch BN semantics of single-device gradient
   accumulation exactly. This is the capability the reference runs as its
   headline distributed benchmark (WRN-16-8 CIFAR-100 through a multi-stage
   pipeline, sample_logs/cifar100_wrn16_8) — there via per-hop TCP/RDMA
   serialization, here as one XLA program with zero host round trips.

3. ``StagePipeline`` — the generality path mirroring the reference's
   coordinator/worker shape: each stage a separate jitted program on its own
   device, microbatches flowing via device-to-device transfers, JAX async
   dispatch overlapping stages like the reference's semi-async schedule.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib


# ---------------------------------------------------------------------------
# 1. Compiled SPMD pipeline (shard_map + ppermute + scan)
# ---------------------------------------------------------------------------


def _homogeneous_pipeline_setup(block_fn, stacked_params, x_microbatches,
                                mesh: Mesh, axis: str):
    """Shared validation + activation-shape inference for the homogeneous
    compiled pipelines (spmd_pipeline / spmd_pipeline_interleaved).

    Returns (pp, num_mb, act) where ``act`` is the per-microbatch activation
    ShapeDtypeStruct every stage must preserve."""
    pp = mesh_lib.axis_size(mesh, axis)
    num_mb = x_microbatches.shape[0]
    if num_mb < 1:
        raise ValueError("need at least one microbatch")
    stage0 = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
    act = jax.eval_shape(block_fn, stage0, jax.ShapeDtypeStruct(
        x_microbatches.shape[1:], x_microbatches.dtype))
    if act.shape != x_microbatches.shape[1:]:
        raise ValueError(f"pipeline stages must preserve activation shape, got "
                         f"{x_microbatches.shape[1:]} -> {act.shape}")
    return pp, num_mb, act


def spmd_pipeline(block_fn: Callable, stacked_params, x_microbatches, mesh: Mesh,
                  axis: str = "pipe"):
    """Run microbatches through a chain of identical-structure stages.

    Args:
      block_fn: (stage_params, activation) -> activation. stage_params is one slice of
        ``stacked_params`` along its leading axis (a stage may hold several layers —
        stack them inside and scan in block_fn).
      stacked_params: pytree; every leaf has leading dim == mesh pipe size.
      x_microbatches: (num_mb, mb_size, ...) inputs to stage 0.
      mesh: mesh containing ``axis``.

    Returns: (num_mb, mb_size, ...) outputs of the last stage.
    Differentiable end-to-end.
    """
    pp, num_mb, act = _homogeneous_pipeline_setup(
        block_fn, stacked_params, x_microbatches, mesh, axis)

    def per_device(params, xs):
        # shard_map keeps the sharded leading dim at local size 1 — drop it
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        # xs: full microbatch queue (replicated)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        zero = jnp.zeros(mb_shape, act.dtype)
        outputs0 = jnp.zeros((num_mb,) + mb_shape, act.dtype)

        def tick(carry, t):
            recv, outputs = carry
            inject = xs[jnp.minimum(t, num_mb - 1)].astype(act.dtype)
            inp = jnp.where(stage == 0, inject, recv)
            out = block_fn(params, inp).astype(act.dtype)
            # last stage: record mb (t - (pp-1)) when valid
            out_idx = t - (pp - 1)
            valid = jnp.logical_and(stage == pp - 1,
                                    jnp.logical_and(out_idx >= 0, out_idx < num_mb))
            idx = jnp.clip(out_idx, 0, num_mb - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, idx, 0, keepdims=False)
            upd = jnp.where(valid, out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(outputs, upd, idx, 0)
            # hop to the next stage over ICI
            perm = [(i, (i + 1) % pp) for i in range(pp)]
            recv = jax.lax.ppermute(out, axis, perm)
            return (recv, outputs), None

        (recv, outputs), _ = jax.lax.scan(
            tick, (zero, outputs0), jnp.arange(num_mb + pp - 1))
        return outputs[None]  # re-add pipe dim for out_specs

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stacked_params), P())
    out_specs = P(axis)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    stacked_out = fn(stacked_params, x_microbatches)  # (pp, num_mb, ...)
    return stacked_out[-1]


def stack_stage_params(per_stage_params: Sequence) -> Any:
    """Stack a list of identical-structure stage params into one pytree with a leading
    stage axis (the SPMD pipeline's input layout)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def spmd_pipeline_interleaved(block_fn: Callable, stacked_params, x_microbatches,
                              mesh: Mesh, axis: str = "pipe",
                              virtual: int = 2):
    """Interleaved (Megatron-style) schedule: beats the plain GPipe bubble.

    The reference's best schedule is semi-async 1F1B (coordinator.hpp:165-223),
    whose bubble equals GPipe's — only INTERLEAVING virtual stages shrinks it.
    Here the L = virtual*pp stages place round-robin (stage s on device s%pp),
    so each device holds ``virtual`` chunks of 1/v the work; the bubble drops
    from (pp-1)*T to (pp-1)*T/v.

    This maps onto a compiled lockstep scan because the interleaved forward
    schedule is TIGHT: with sub-tick
        tau(s=c*pp+d, m) = d + (m %% pp) + pp*(c + v*(m // pp))
    every stage's input arrives over ICI exactly at the sub-tick it is
    consumed (the chunk-boundary hop d=pp-1 -> d=0 has slack 1, same as the
    in-chunk hop), so no inter-stage queues exist — one ppermute per sub-tick
    and a dynamic chunk-select per device. jax.grad transposes the scan into
    the interleaved backward.

    Args mirror ``spmd_pipeline`` with ``stacked_params`` leading dim
    L = virtual * pp (stage s params at index s). num_mb must be a multiple
    of pp (Megatron's constraint — the round-robin rounds must fill).
    """
    v = int(virtual)
    pp, num_mb, act = _homogeneous_pipeline_setup(
        block_fn, stacked_params, x_microbatches, mesh, axis)
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if v < 1 or L != v * pp:
        raise ValueError(f"stacked_params leading dim {L} != virtual {v} * pipe {pp}")
    if num_mb % pp:
        raise ValueError(f"interleaved schedule needs num_microbatches "
                         f"({num_mb}) divisible by pipe size ({pp})")
    # round-robin placement: device d's local chunk c is global stage c*pp + d,
    # so re-order rows to (d*v + c) before sharding the leading axis over pp
    order = np.argsort([(s % pp) * v + s // pp for s in range(L)], kind="stable")
    placed = jax.tree_util.tree_map(lambda a: a[order], stacked_params)
    # last sub-tick: stage L-1 = (c=v-1, d=pp-1) processing mb num_mb-1
    n_ticks = ((pp - 1) + ((num_mb - 1) % pp)
               + pp * ((v - 1) + v * ((num_mb - 1) // pp)) + 1)

    def per_device(params, xs):
        # local params: (v, ...) — this device's chunks; chunk c = stage c*pp+d
        d = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        outputs0 = jnp.zeros((num_mb,) + mb_shape, act.dtype)
        zero = jnp.zeros(mb_shape, act.dtype)
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(carry, u):
            recv, outputs = carry
            # invert tau: which (chunk c, microbatch m) does device d run now?
            w = u - d
            q, j = w // pp, jnp.mod(w, pp)
            c = jnp.mod(q, v)
            m = (q // v) * pp + j
            active = jnp.logical_and(w >= 0, m < num_mb)
            m_idx = jnp.clip(m, 0, num_mb - 1)
            inject = jnp.logical_and(c == 0, d == 0)
            inp = jnp.where(inject, xs[m_idx].astype(act.dtype), recv)
            chunk = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                params)
            out = block_fn(chunk, inp).astype(act.dtype)
            emit = jnp.logical_and(active,
                                   jnp.logical_and(c == v - 1, d == pp - 1))
            cur = jax.lax.dynamic_index_in_dim(outputs, m_idx, 0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(emit, out, cur), m_idx, 0)
            recv = jax.lax.ppermute(out, axis, perm)
            return (recv, outputs), None

        (recv, outputs), _ = jax.lax.scan(
            tick, (zero, outputs0), jnp.arange(n_ticks))
        return outputs[None]

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), placed), P())
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis), check_vma=False)
    stacked_out = fn(placed, x_microbatches)  # (pp, num_mb, ...)
    return stacked_out[-1]


# ---------------------------------------------------------------------------
# 2. Compiled heterogeneous-stage pipeline (shape-changing stages, correct BN)
# ---------------------------------------------------------------------------


class _TreeCodec:
    """Pack/unpack a fixed-structure pytree into one flat f32 vector.

    Static metadata (treedef + per-leaf shape/dtype/offset) is captured once at
    init; packing casts every leaf to f32 (lossless for f32/bf16 params and the
    f32 BatchNorm stats used here) so heterogeneous stage structures become
    uniform (pp, max_len) rows shardable over the pipe mesh axis.
    """

    def __init__(self, template):
        leaves, self.treedef = jax.tree_util.tree_flatten(template)
        self.info = []
        off = 0
        for leaf in leaves:
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            self.info.append((tuple(leaf.shape), jnp.dtype(leaf.dtype), off, n))
            off += n
        self.size = off

    def pack(self, tree, padded_len: int) -> jax.Array:
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return jnp.zeros((padded_len,), jnp.float32)
        vec = jnp.concatenate(
            [jnp.ravel(x).astype(jnp.float32) for x in leaves])
        return jnp.pad(vec, (0, padded_len - vec.shape[0]))

    def unpack(self, vec: jax.Array):
        leaves = [vec[o:o + n].reshape(shape).astype(dt)
                  for shape, dt, o, n in self.info]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


class HeteroPipeline:
    """Compile-time plan for a heterogeneous-stage SPMD pipeline.

    Built from a list of stage Modules (e.g. ``partitioner.partition_model``
    output). Owns the static metadata — per-stage activation shapes from shape
    propagation, packed param/state codecs, buffer sizes — and provides
    ``pipeline_loss``, the differentiable (packed_params, packed_state, data,
    labels, rng) -> (loss, aux) function whose jax.grad IS the backward
    pipeline (ppermute transposes to the reverse hop; the scan's saved
    residuals are the per-microbatch activation caches the reference keeps by
    hand, include/nn/layer.hpp:113-114).
    """

    def __init__(self, stages: Sequence, mesh: Mesh, input_shape,
                 input_dtype=jnp.bfloat16, num_microbatches: int = 4,
                 axis: str = "pipe", loss_fn: Optional[Callable] = None,
                 compute_accuracy: bool = True, data_axis: Optional[str] = None,
                 remat: "bool | str" = False, virtual: int = 1):
        from ..nn import losses as losses_lib

        self.stages = list(stages)
        self.mesh = mesh
        self.axis = axis
        self.pp = mesh_lib.axis_size(mesh, axis)
        self.v = int(virtual)
        # dp x pp in ONE program: the microbatch batch dim shards over the data
        # axis (each data rank pipelines its slice; grads auto-psum because the
        # params are replicated over data in the shard_map in_specs). The
        # reference offers dp OR pp per run, never composed — and its dp never
        # all-reduces (coordinator.hpp:37-40).
        self.data_axis = data_axis if (
            data_axis and mesh_lib.axis_size(mesh, data_axis) > 1) else None
        self.dp = mesh_lib.axis_size(mesh, data_axis) if self.data_axis else 1
        # input_shape is the per-microbatch GLOBAL shape; stages see local slices
        if self.dp > 1:
            if input_shape[0] % self.dp:
                raise ValueError(f"microbatch size {input_shape[0]} not "
                                 f"divisible by data axis {self.dp}")
            input_shape = (input_shape[0] // self.dp,) + tuple(input_shape[1:])
        if self.v * self.pp != len(self.stages):
            raise ValueError(f"{len(self.stages)} stages != virtual {self.v} "
                             f"x mesh {axis} size {self.pp}")
        self.L = len(self.stages)  # global stage count (v chunks per device)
        self.num_mb = int(num_microbatches)
        if self.v > 1 and self.num_mb % self.pp:
            raise ValueError(f"interleaved schedule needs num_microbatches "
                             f"({self.num_mb}) divisible by pipe ({self.pp})")
        # device-order row layout: row r = d*v + c holds global stage c*pp + d,
        # so sharding the leading axis over pipe gives device d its v chunks
        # contiguously (identity when v == 1)
        self._stage_of_row = [(r % self.v) * self.pp + r // self.v
                              for r in range(self.L)]
        if isinstance(loss_fn, (str, dict)) or loss_fn is None:
            loss_fn = losses_lib.get(loss_fn or "softmax_cross_entropy")
        self.loss_fn = loss_fn
        self.compute_accuracy = bool(compute_accuracy)
        # Schedule note: v == 1 is compiled lockstep GPipe — bubble fraction
        # (pp-1)/(num_mb+pp-1). Event-driven 1F1B (the reference's semi-async
        # schedule, coordinator.hpp:165-223) has the SAME bubble as GPipe; its
        # memory benefit comes here from ``remat=True`` (saved activations per
        # tick shrink to the hop buffers), and hops cost ~0 (ICI ppermute
        # inside one XLA program vs per-hop TCP/RDMA serialization), so
        # num_mb can be raised until the bubble vanishes. ``virtual=v > 1``
        # runs the interleaved (Megatron-style) schedule — device d holds the
        # v chunks c*pp+d, and the bubble drops to (pp-1)/v stage-times: with
        # sub-tick tau(s=c*pp+d, m) = d + (m%%pp) + pp*(c + v*(m//pp)) every
        # hop (in-chunk d->d+1 AND chunk-boundary pp-1->0) has slack exactly
        # 1, so one ppermute per sub-tick suffices and the whole schedule
        # stays a single compiled scan (same tightness argument as
        # ``spmd_pipeline_interleaved``, here with heterogeneous stages).
        # bool OR a policy name ("dots", ...) — resolved once here so a typo
        # raises at build time on this path too (train.step.resolve_remat_policy)
        self.remat = bool(remat)
        self._remat_policy = None
        if remat:
            from ..train.step import resolve_remat_policy

            self._remat_policy = resolve_remat_policy(remat)

        # shape propagation (parity: deploy_stages shape chain,
        # coordinator.hpp:368-456): microbatch-shaped activations per boundary
        self.in_shapes: List[Tuple[int, ...]] = []
        self.in_dtypes: List[Any] = []
        shape, dtype = tuple(input_shape), jnp.dtype(input_dtype)
        self._init_shape0 = shape
        rng0 = jax.random.PRNGKey(0)
        self._stage_vars_shape = []
        for stage in self.stages:
            self.in_shapes.append(shape)
            self.in_dtypes.append(dtype)
            v_shape = jax.eval_shape(
                lambda s=stage, sh=shape: s.init(rng0, sh))
            out = jax.eval_shape(
                lambda v, x, s=stage: s.apply(v, x, train=False)[0],
                v_shape, jax.ShapeDtypeStruct(shape, dtype))
            self._stage_vars_shape.append(v_shape)
            shape, dtype = out.shape, out.dtype
        self.out_shape, self.out_dtype = shape, dtype

        # packed-row codecs; rows padded to the widest stage
        self.p_codecs = [_TreeCodec(v["params"]) for v in self._stage_vars_shape]
        self.s_codecs = [_TreeCodec(v["state"]) for v in self._stage_vars_shape]
        self.p_len = max(max(c.size for c in self.p_codecs), 1)
        self.s_len = max(max(c.size for c in self.s_codecs), 1)
        # activation hop buffer: elements of the widest boundary, one dtype wide
        # enough for every boundary (bf16 boundaries stay bf16; mixed promotes)
        self.buf_elems = max(int(np.prod(s)) for s in self.in_shapes[1:] + [self.out_shape]) \
            if self.pp > 1 else int(np.prod(self.out_shape))
        self.buf_dtype = self.in_dtypes[1] if self.pp > 1 else self.out_dtype
        for d in self.in_dtypes[2:] + [self.out_dtype]:
            self.buf_dtype = jnp.promote_types(self.buf_dtype, d)
        # the stage-0 injection rides the same buffer: its dtype must survive
        # the round trip. Integer inputs (token ids) go through f32 — exact for
        # ids < 2^24 — because jax's lattice would otherwise pick bf16 and
        # silently round ids > 256.
        d0 = self.in_dtypes[0]
        if jnp.issubdtype(d0, jnp.integer):
            self.buf_dtype = jnp.promote_types(self.buf_dtype, jnp.float32)
        else:
            self.buf_dtype = jnp.promote_types(self.buf_dtype, d0)
        # stage-0 injection buffer must fit the raw input too
        self.buf_elems = max(self.buf_elems, int(np.prod(self.in_shapes[0])))

    # -- state management -----------------------------------------------------

    def init_packed(self, rng: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Initialize every stage and pack into ((L, p_len), (L, s_len)) rows
        in DEVICE order (row d*v + c = stage c*pp + d), placed sharded over
        the pipe axis."""
        keys = jax.random.split(rng, self.L)
        vars_by_stage = [stage.init(keys[i], self.in_shapes[i])
                         for i, stage in enumerate(self.stages)]
        return self.pack_stage_variables(vars_by_stage)

    def unpack_stage_variables(self, packed_params, packed_state) -> List[dict]:
        """Back to per-stage {"params", "state"} pytrees in GLOBAL stage order
        (checkpoint/export)."""
        pr = np.asarray(packed_params)
        sr = np.asarray(packed_state)
        out = [None] * self.L
        for r, s in enumerate(self._stage_of_row):
            out[s] = {"params": self.p_codecs[s].unpack(jnp.asarray(pr[r])),
                      "state": self.s_codecs[s].unpack(jnp.asarray(sr[r]))}
        return out

    def place_train_state(self, state):
        """Re-apply the pipe-axis sharding to a TrainState whose leaves lost
        placement (e.g. after a checkpoint restore loads host arrays)."""
        rows = NamedSharding(self.mesh, P(self.axis))

        def place(x):
            spec = P(self.axis) if getattr(x, "ndim", 0) >= 1 else P()
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return state._replace(
            params=jax.device_put(state.params, rows),
            opt_state=jax.tree_util.tree_map(place, state.opt_state),
            net_state=jax.device_put(state.net_state, rows))

    def pack_stage_variables(self, variables: Sequence[dict]):
        """Inverse of unpack: per-stage variables (global order) -> device-order
        packed rows (restore from a per-stage checkpoint)."""
        sharding = NamedSharding(self.mesh, P(self.axis))
        p = jnp.stack([self.p_codecs[s].pack(variables[s]["params"], self.p_len)
                       for s in self._stage_of_row])
        s_ = jnp.stack([self.s_codecs[s].pack(variables[s]["state"], self.s_len)
                        for s in self._stage_of_row])
        return jax.device_put(p, sharding), jax.device_put(s_, sharding)

    # -- the compiled schedule ------------------------------------------------

    def _encode(self, x) -> jax.Array:
        flat = jnp.ravel(x).astype(self.buf_dtype)
        return jnp.pad(flat, (0, self.buf_elems - flat.shape[0]))

    def _make_branch(self, i: int, train: bool):
        """Branch i of the per-tick lax.switch: decode this stage's input from
        the hop buffer, run the stage, encode the output, and (last stage only)
        compute loss/corrects against the tick's labels."""
        stage = self.stages[i]
        in_shape, in_dtype = self.in_shapes[i], self.in_dtypes[i]
        p_codec, s_codec = self.p_codecs[i], self.s_codecs[i]
        is_last = i == self.L - 1

        def run_stage(p_vec, s_vec, x, key):
            from ..train.step import aux_loss_sum

            variables = {"params": p_codec.unpack(p_vec),
                         "state": s_codec.unpack(s_vec)}
            out, new_state = stage.apply(variables, x, train=train, rng=key)
            # every stage reports its own aux losses (MoE load balancing,
            # nn/moe.py) — the schedule adds them to the training loss per
            # active microbatch, matching make_train_step's aux_loss_sum
            aux = aux_loss_sum(new_state) if train else jnp.zeros(
                (), jnp.float32)
            return out, s_codec.pack(new_state, self.s_len), aux

        if self.remat and train:
            if self._remat_policy is None:
                run_stage = jax.checkpoint(run_stage)
            else:
                run_stage = jax.checkpoint(run_stage,
                                           policy=self._remat_policy)

        def branch(p_vec, s_vec, buf, labels_mb, key):
            x = buf[:int(np.prod(in_shape))].reshape(in_shape).astype(in_dtype)
            out, new_s_vec, aux = run_stage(p_vec, s_vec, x, key)
            if is_last:
                loss = self.loss_fn(out, labels_mb).astype(jnp.float32) + aux
                if self.compute_accuracy:
                    from ..nn import metrics as metrics_lib

                    corr = metrics_lib.class_corrects(out, labels_mb).astype(
                        jnp.float32)
                else:
                    corr = jnp.zeros((), jnp.float32)
            else:
                loss = aux
                corr = jnp.zeros((), jnp.float32)
            return self._encode(out), new_s_vec, loss, corr

        return branch

    def _prep(self, data, labels, train: bool):
        """Shared prologue: reshape the batch to (num_mb, mb_global, ...) and
        build the per-tick switch branches + tick count."""
        num_mb, pp, v = self.num_mb, self.pp, self.v
        mb = self.in_shapes[0][0]  # LOCAL microbatch size (per data shard)
        mb_global = mb * self.dp
        if data.shape[0] != num_mb:
            if data.shape[0] != num_mb * mb_global:
                raise ValueError(f"batch {data.shape[0]} != num_microbatches "
                                 f"{num_mb} x microbatch {mb_global}")
            data = data.reshape((num_mb, mb_global) + data.shape[1:])
            labels = labels.reshape((num_mb, mb_global) + labels.shape[1:])
        branches = [self._make_branch(i, train) for i in range(self.L)]
        if v == 1:
            n_ticks = num_mb + pp - 1
        else:
            # last sub-tick: stage L-1 = (c=v-1, d=pp-1) on microbatch num_mb-1
            n_ticks = ((pp - 1) + ((num_mb - 1) % pp)
                       + pp * ((v - 1) + v * ((num_mb - 1) // pp)) + 1)
        return data, labels, mb_global, branches, n_ticks

    def _device_schedule(self, branches, n_ticks, p_rows, s_rows, data_mb,
                         labels_mb, key):
        """The fill/drain schedule for ONE device; call inside shard_map.

        Returns (new state rows, loss sum, corrects sum) — data-axis
        reductions already applied, so all three are data-axis invariant."""
        num_mb, pp, axis, v = self.num_mb, self.pp, self.axis, self.v
        d = jax.lax.axis_index(axis)
        if self.data_axis is not None:
            # distinct dropout masks per data shard — without this every
            # shard would reuse the replicated key on different samples
            key = jax.random.fold_in(key, jax.lax.axis_index(self.data_axis))
        # encode all injected microbatches once (stage c=0, d=0 consumes)
        inject = jax.vmap(self._encode)(data_mb)

        def tick(carry, t):
            recv, s_rows_l, loss_acc, corr_acc = carry
            if v == 1:
                c = jnp.zeros((), jnp.int32)
                m = t - d
                active = jnp.logical_and(d <= t, m < num_mb)
            else:
                # invert tau: which (chunk c, microbatch m) runs now?
                w = t - d
                q, j = w // pp, jnp.mod(w, pp)
                c = jnp.mod(q, v)
                m = (q // v) * pp + j
                active = jnp.logical_and(w >= 0, m < num_mb)
            m_idx = jnp.clip(m, 0, num_mb - 1)
            inject_here = jnp.logical_and(c == 0, d == 0)
            inp = jnp.where(inject_here, inject[m_idx], recv)
            s_vec = jax.lax.dynamic_index_in_dim(s_rows_l, c, 0,
                                                 keepdims=False)
            p_vec = jax.lax.dynamic_index_in_dim(p_rows, c, 0,
                                                 keepdims=False)
            gstage = c * pp + d
            key_t = jax.random.fold_in(jax.random.fold_in(key, t), gstage)
            out_buf, new_s, loss, corr = jax.lax.switch(
                gstage, branches, p_vec, s_vec, inp, labels_mb[m_idx],
                key_t)
            # a stage holds a real microbatch only during its active window;
            # outside it the input is schedule garbage — state/loss must not
            # absorb it (this is what keeps BatchNorm statistics exact)
            s_rows_l = jax.lax.dynamic_update_index_in_dim(
                s_rows_l, jnp.where(active, new_s, s_vec), c, 0)
            # every ACTIVE stage contributes (non-last stages return their
            # aux losses only — 0 unless the stage carries MoE routing);
            # accuracy still comes from the emitting last stage alone
            emit = jnp.logical_and(
                active, jnp.logical_and(d == pp - 1, c == v - 1))
            loss_acc = loss_acc + jnp.where(active, loss, 0.0)
            corr_acc = corr_acc + jnp.where(emit, corr, 0.0)
            perm = [(i, (i + 1) % pp) for i in range(pp)]
            recv = jax.lax.ppermute(out_buf, axis, perm)
            return (recv, s_rows_l, loss_acc, corr_acc), None

        zero_buf = jnp.zeros((self.buf_elems,), self.buf_dtype)
        (recv, s_rows_l, loss_acc, corr_acc), _ = jax.lax.scan(
            tick, (zero_buf, s_rows, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)),
            jnp.arange(n_ticks))
        if self.data_axis is not None:
            # data ranks saw different samples: average the running-stat
            # updates (sync-BN-style state merge; normalization itself used
            # per-shard batch stats — standard "ghost BN" dp semantics) and
            # reduce loss/corrects so outputs are data-axis invariant
            s_rows_l = jax.lax.pmean(s_rows_l, self.data_axis)
            loss_acc = jax.lax.pmean(loss_acc, self.data_axis)
            corr_acc = jax.lax.psum(corr_acc, self.data_axis)
        # local (v, s_len) state rows; caller decides how to expose them
        return s_rows_l, loss_acc, corr_acc

    def _in_specs(self):
        dp_ax = self.data_axis
        return (P(self.axis), P(self.axis), P(None, dp_ax), P(None, dp_ax),
                P())

    def _collect(self, losses, corrects, mb_global):
        """Device-concatenated per-device sums -> (mean loss, metrics)."""
        # summing over devices collects the last stage's data losses AND every
        # stage's aux losses, averaged per microbatch — the same total
        # make_train_step's loss_fn + aux_loss_sum produces under grad accum
        loss = jnp.sum(losses) / self.num_mb
        metrics = {"loss": loss}
        if self.compute_accuracy:
            metrics["accuracy"] = jnp.sum(corrects) / (self.num_mb * mb_global)
        return loss, metrics

    def pipeline_loss(self, packed_params, packed_state, data, labels, rng,
                      train: bool = True):
        """(mean loss over microbatches, (new_packed_state, metrics)).

        ``data``: (num_mb * mb, ...) or (num_mb, mb, ...); labels likewise.
        Differentiable w.r.t. packed_params. Run under ``self.mesh``.
        """
        data, labels, mb_global, branches, n_ticks = self._prep(
            data, labels, train)

        def per_device(p_rows, s_rows, data_mb, labels_mb, key):
            s_rows_l, loss_acc, corr_acc = self._device_schedule(
                branches, n_ticks, p_rows, s_rows, data_mb, labels_mb, key)
            # local (v, s_len) rows concatenate over pipe to (L, s_len)
            return s_rows_l, loss_acc[None], corr_acc[None]

        fn = jax.shard_map(
            per_device, mesh=self.mesh, in_specs=self._in_specs(),
            out_specs=(P(self.axis), P(self.axis), P(self.axis)),
            check_vma=False)
        new_state, losses, corrects = fn(packed_params, packed_state, data,
                                         labels, rng)
        loss, metrics = self._collect(losses, corrects, mb_global)
        return loss, (new_state, metrics)

    def pipeline_value_and_grad(self, packed_params, packed_state, data,
                                labels, rng):
        """(loss, new_packed_state, metrics, grads) for one train batch.

        Same math as ``jax.value_and_grad(pipeline_loss)``, but the VJP runs
        INSIDE the shard_map body: each device differentiates the global
        scalar loss (psum over pipe of its schedule's contribution) w.r.t.
        its own packed rows, with the collectives transposed per device
        (ppermute -> inverse permutation, psum -> identity + a manual psum of
        the row grads over the data axis). shard_map's own transpose rule is
        never invoked (no scalar residual out-specs, no symbolic-zero
        cotangents to thread through it) and the path stays exactly as
        parallel.
        """
        data, labels, mb_global, branches, n_ticks = self._prep(
            data, labels, True)

        def per_device(p_rows, s_rows, data_mb, labels_mb, key):
            def local_loss(p):
                s_l, loss_acc, corr_acc = self._device_schedule(
                    branches, n_ticks, p, s_rows, data_mb, labels_mb, key)
                # the SAME global scalar on every device: sum each device's
                # (data-reduced) contribution over the pipe ring
                gloss = jax.lax.psum(loss_acc, self.axis) / self.num_mb
                return gloss, (s_l, loss_acc, corr_acc)

            (_, (s_l, loss_acc, corr_acc)), gp = jax.value_and_grad(
                local_loss, has_aux=True)(p_rows)
            if self.data_axis is not None:
                # per-device psum transpose is identity, so gp holds only this
                # data shard's term of d(loss)/d(rows) — sum the shards
                gp = jax.lax.psum(gp, self.data_axis)
            return gp, s_l, loss_acc[None], corr_acc[None]

        fn = jax.shard_map(
            per_device, mesh=self.mesh, in_specs=self._in_specs(),
            out_specs=(P(self.axis),) * 4, check_vma=False)
        grads, new_state, losses, corrects = fn(
            packed_params, packed_state, data, labels, rng)
        loss, metrics = self._collect(losses, corrects, mb_global)
        return loss, new_state, metrics, grads


def make_pipeline_train_step(stages: Sequence, optimizer, mesh: Mesh,
                             input_shape, *, loss_fn=None,
                             num_microbatches: int = 4, axis: str = "pipe",
                             input_dtype=jnp.bfloat16, scheduler=None,
                             donate: bool = True, compute_accuracy: bool = True,
                             data_axis: Optional[str] = None,
                             augment: Optional[Callable] = None,
                             remat: "bool | str" = False, virtual: int = 1):
    """Config-to-running-pipeline in one call (parity: the reference's
    coordinator deploy + async_train_batch + UPDATE_PARAMETERS cycle,
    coordinator.hpp:165-223, as ONE jitted program).

    ``virtual=v > 1`` selects the interleaved schedule: pass v*pp stages and
    the GPipe bubble shrinks to (pp-1)/v stage-times.
    ``input_shape`` is the per-MICROBATCH input shape (mb, H, W, C).
    Returns ``(pipe, step_fn, init_fn)``:
      * ``init_fn(rng) -> TrainState`` — packed params/state sharded over pipe,
        optimizer state over the packed rows (elementwise optimizers are
        leaf-order invariant, so packed updates match per-tree updates exactly).
      * ``step_fn(state, data, labels) -> (state, metrics)`` — full batch of
        num_microbatches * mb samples through fill/drain, grads from jax.grad
        of the schedule, one optimizer update (microbatch gradient
        accumulation, parity: distributed/train.hpp:19-79).
    """
    from ..nn.schedulers import NoOp
    from ..train.step import TrainState

    pipe = HeteroPipeline(stages, mesh, input_shape, input_dtype=input_dtype,
                          num_microbatches=num_microbatches, axis=axis,
                          loss_fn=loss_fn, compute_accuracy=compute_accuracy,
                          data_axis=data_axis, remat=remat, virtual=virtual)
    scheduler = scheduler or NoOp()
    host_driven = getattr(scheduler, "host_driven", False)

    def init_fn(rng: jax.Array) -> TrainState:
        init_rng, step_rng = jax.random.split(rng)
        p, s = pipe.init_packed(init_rng)
        state = TrainState(params=p, opt_state=optimizer.init(p), net_state=s,
                           step=jnp.zeros((), jnp.int32), rng=step_rng)
        return pipe.place_train_state(state)  # one placement rule for init+resume

    def step(state: TrainState, data, labels, lr_scale):
        rng, aug_rng, sub = jax.random.split(state.rng, 3)
        if augment is not None:  # on-device augmentation, fused into the step
            data = augment(aug_rng, data)
        # the schedule averages over microbatches, so grads carry the 1/num_mb
        # factor — same math as single-device gradient accumulation
        loss, new_net, metrics, grads = pipe.pipeline_value_and_grad(
            state.params, state.net_state, data, labels, sub)
        if not host_driven:
            lr_scale = scheduler.scale(state.step)
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params, lr_scale=lr_scale)
        metrics = dict(metrics, lr_scale=lr_scale)
        return TrainState(new_params, new_opt, new_net,
                          state.step + 1, rng), metrics

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())

    if host_driven:
        def step_fn(state, data, labels):
            with mesh:
                return jitted(state, data, labels,
                              jnp.asarray(scheduler.current_scale(), jnp.float32))
    else:
        one = jnp.ones((), jnp.float32)  # hoisted: no per-step H2D transfer

        def step_fn(state, data, labels):
            with mesh:
                return jitted(state, data, labels, one)

    return pipe, step_fn, init_fn


def make_pipeline_eval_step(pipe: HeteroPipeline):
    """Jitted (state, data, labels) -> metrics through the same pipeline
    (train=False: BatchNorm uses running stats, no state mutation)."""

    def ev(state, data, labels):
        _, (_, metrics) = pipe.pipeline_loss(
            state.params, state.net_state, data, labels,
            jax.random.PRNGKey(0), False)
        if "accuracy" in metrics:
            mb_global = pipe.in_shapes[0][0] * pipe.dp
            metrics = dict(metrics, corrects=metrics.pop("accuracy")
                           * (pipe.num_mb * mb_global))
        return metrics

    jitted = jax.jit(ev)

    def eval_fn(state, data, labels):
        with pipe.mesh:
            return jitted(state, data, labels)

    return eval_fn


# ---------------------------------------------------------------------------
# 3. Host-orchestrated heterogeneous-stage pipeline
# ---------------------------------------------------------------------------


class StagePipeline:
    """Generic pipeline over heterogeneous stage modules, one device each.

    The TPU-native analog of the reference's coordinator+workers (SURVEY.md §3.2):
    CONFIG_TRANSFER -> constructor; FORWARD_JOB/BACKWARD_JOB -> jitted per-stage
    programs + async dispatch; TCP/RoCE hops -> jax.device_put over ICI.
    """

    def __init__(self, stages: Sequence, optimizer, loss_fn, devices=None):
        self.stages = list(stages)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._step_count = 0  # advances the default dropout rng per step
        devices = list(devices) if devices is not None else jax.devices()
        if len(devices) < len(self.stages):
            raise ValueError(f"{len(self.stages)} stages need as many devices, "
                             f"have {len(devices)}")
        self.devices = devices[:len(self.stages)]
        self.variables: List[Any] = []
        self.opt_states: List[Any] = []
        self._fwd = []
        self._fwd_train = []
        for i, stage in enumerate(self.stages):
            # pure apply for vjp; net state is a real argument (closing over it
            # would bake it into the compiled program and ignore later updates)
            def apply_fn(params, net_state, x, stage=stage):
                out, _ = stage.apply({"params": params, "state": net_state},
                                     x, train=False)
                return out

            def apply_train(params, net_state, x, key, stage=stage):
                # train=True with the new state as aux: BatchNorm statistics
                # update per microbatch exactly like single-device training
                # (the earlier train=False here silently froze BN — a WRN
                # through this pipeline would normalize with init-time stats
                # forever)
                out, new_state = stage.apply(
                    {"params": params, "state": net_state}, x, train=True,
                    rng=key)
                return out, new_state

            # params are committed to the stage's device, so the jitted program runs there
            self._fwd.append(jax.jit(apply_fn))
            self._fwd_train.append(jax.jit(apply_train))

    def init(self, rng, input_shape, input_dtype=None):
        """Initialize every stage, placing its params on its device
        (parity: deploy_stages, coordinator.hpp:368)."""
        shape = tuple(input_shape)
        dtype = input_dtype
        self.variables, self.opt_states = [], []
        keys = jax.random.split(rng, len(self.stages))
        for i, stage in enumerate(self.stages):
            if dtype is not None:
                v = stage.init(keys[i], shape, input_dtype=dtype)
            else:
                v = stage.init(keys[i], shape)
            v = jax.device_put(v, self.devices[i])
            self.variables.append(v)
            self.opt_states.append(
                jax.device_put(self.optimizer.init(v["params"]), self.devices[i]))
            dummy = jax.ShapeDtypeStruct(tuple(shape), dtype or jnp.float32)
            out = jax.eval_shape(self._fwd[i], v["params"], v["state"], dummy)
            shape, dtype = out.shape, out.dtype
        return self

    def forward(self, x):
        """Inference pass: microbatch-free, stage hop = device transfer."""
        for i in range(len(self.stages)):
            x = jax.device_put(x, self.devices[i])
            x = self._fwd[i](self.variables[i]["params"], self.variables[i]["state"], x)
        return x

    def train_batch(self, data, labels, num_microbatches: int = 4, rng=None):
        """One training step: GPipe fill/drain with gradient accumulation
        (parity: async_train_batch, coordinator.hpp:165-223 + distributed/train.hpp:19-79).

        Async dispatch overlaps stage work across microbatches without explicit
        scheduling — the queueing the reference does by hand. BatchNorm state
        threads through the microbatches (mb k normalizes with mb k's batch
        stats and updates the running stats mb k-1 left), matching
        single-device gradient accumulation.

        Returns the mean microbatch loss as a DEVICE scalar — fetching it
        (float()) is the caller's sync point; doing it here would serialize
        every step boundary on the host.
        """
        n = len(self.stages)
        mbs = jnp.split(data, num_microbatches)
        lbs = jnp.split(labels, num_microbatches)
        grads = [None] * n
        if rng is None:
            # default rng advances per step — a fixed key would apply the SAME
            # dropout mask on every training step
            rng = jax.random.fold_in(jax.random.PRNGKey(0), self._step_count)
        self._step_count += 1

        # fill: forward all microbatches, keeping vjp closures (activation
        # residuals) and threading each stage's mutable state forward
        states = [v["state"] for v in self.variables]
        vjps = []  # [mb][stage]
        outs = []
        for m, mb in enumerate(mbs):
            stage_vjps = []
            x = mb
            for i in range(n):
                x = jax.device_put(x, self.devices[i])
                fwd, st = self._fwd_train[i], states[i]
                key = jax.random.fold_in(jax.random.fold_in(rng, m), i)
                x, vjp, new_st = jax.vjp(
                    lambda p, xx, fwd=fwd, st=st, k=key: fwd(p, st, xx, k),
                    self.variables[i]["params"], x, has_aux=True)
                states[i] = new_st
                stage_vjps.append(vjp)
            vjps.append(stage_vjps)
            outs.append(x)
        for i in range(n):
            self.variables[i] = {"params": self.variables[i]["params"],
                                 "state": states[i]}

        # drain: loss grad per microbatch, backward through stages in reverse
        scale = 1.0 / num_microbatches
        losses = []
        for out, lb, stage_vjps in zip(outs, lbs, vjps):
            lb = jax.device_put(lb, self.devices[-1])
            loss, loss_vjp = jax.vjp(lambda o: self.loss_fn(o, lb), out)
            losses.append(loss)  # keep on device — a float() here would stall the pipeline
            (g,) = loss_vjp(jnp.asarray(scale, jnp.float32))
            for i in reversed(range(n)):
                g = jax.device_put(g, self.devices[i])
                gp, g = stage_vjps[i](g)
                grads[i] = gp if grads[i] is None else jax.tree_util.tree_map(
                    jnp.add, grads[i], gp)

        # optimizer step per stage (parity: UPDATE_PARAMETERS, worker.hpp:194-207)
        for i in range(n):
            new_params, self.opt_states[i] = self.optimizer.update(
                grads[i], self.opt_states[i], self.variables[i]["params"])
            self.variables[i] = {"params": new_params, "state": self.variables[i]["state"]}
        # device scalar: a float() here would sync the host every step and
        # serialize step boundaries; callers fetch when they actually log.
        # (All losses are computed on devices[-1] already — no transfers.)
        return sum(losses[1:], losses[0]) * scale
