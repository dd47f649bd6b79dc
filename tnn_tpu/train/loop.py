"""The full training loop: epochs, validation, checkpointing, metrics.

Parity: reference ``train_model`` (src/nn/train.cpp:367) -> ``train_val`` (:219) /
``train_step`` (:274) -> ``train_epoch`` (:129): per-batch forward/loss/backward/update,
progress prints every N batches with loss/accuracy/ms-per-batch, per-epoch validation
(``validate_model`` :388), best-validation checkpointing to ``model_snapshots/``
(:242-255), RSS memory prints (:269).

TPU-first differences: the per-batch body is ONE compiled XLA program (make_train_step);
batches stream through a background prefetcher that overlaps host assembly + H2D with
device compute; checkpoints capture optimizer/scheduler/loader state so resume is exact
(the reference restarts moments and data order — SURVEY.md §5).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..checkpoint import Checkpoint
from ..data.loader import DataLoader, prefetch
from ..profiling import EventType, GlobalProfiler, profiled
from ..profiling import profiler as _prof_mod
from ..utils.config import TrainingConfig
from ..utils.hardware import memory_usage_kb
from ..utils.logging import get_logger
from .step import TrainState, create_train_state, make_eval_step, make_train_step


def _staged_batches(loader: DataLoader, batch_size: int, config: TrainingConfig,
                    reset: bool = True, limit: int = -1, place=None):
    """io-dtype cast on the producer thread + async device_put, so both the cast and
    the H2D transfer overlap device compute (prefetch's to_device staging).

    ``limit`` bounds the number of batches at the SOURCE (not a consumer-side break):
    the prefetch producer must not advance the loader cursor past what the step loop
    consumes, or mid-epoch checkpoints would record an overshot dataset position.
    """
    import itertools

    import jax.numpy as jnp

    io_dtype = jnp.dtype(config.io_dtype)

    def gen():
        it = loader.batches(batch_size, reset=reset)
        if limit >= 0:
            it = itertools.islice(it, limit)
        for data, labels in it:
            if np.issubdtype(data.dtype, np.floating):
                data = data.astype(io_dtype)
            yield data, labels

    return prefetch(gen(), to_device=place if place is not None else True)


def evaluate(eval_step, state: TrainState, loader: DataLoader, batch_size: int,
             config: Optional[TrainingConfig] = None,
             place=None) -> Dict[str, float]:
    """Full-dataset validation (parity: validate_model, src/nn/train.cpp:388) —
    aggregates corrects/loss over all complete batches."""
    total, corrects, loss_sum, batches = 0, 0.0, 0.0, 0
    cfg = config or TrainingConfig()
    for data, labels in _staged_batches(loader, batch_size, cfg, place=place):
        m = eval_step(state, data, labels)
        loss_sum += float(m["loss"])
        if "corrects" in m:
            corrects += float(m["corrects"])
        total += len(labels)
        batches += 1
    if batches == 0:
        # dataset smaller than one batch (drop-remainder): report honestly rather
        # than a fake perfect loss; NaN also never wins the best-val comparison
        return {"loss": float("nan")}
    out = {"loss": loss_sum / batches}
    if total:
        out["accuracy"] = corrects / total
    return out


def train_model(
    model,
    config: TrainingConfig,
    train_loader: DataLoader,
    val_loader: Optional[DataLoader] = None,
    optimizer=None,
    scheduler=None,
    augment: Optional[Callable] = None,
    state: Optional[TrainState] = None,
    metric_hook: Optional[Callable[[int, Dict[str, Any]], None]] = None,
    state_hook: Optional[Callable[[TrainState], None]] = None,
) -> Tuple[TrainState, List[Dict[str, Any]]]:
    """Train ``model`` per ``config``; returns (final_state, per-epoch history).

    The reference equivalent is train_model (src/nn/train.cpp:367) driving
    train_epoch/validate_model with best-val snapshots.

    ``state_hook`` receives the live TrainState at setup, at every progress-print
    interval, and at each epoch end — it is how a control-plane save RPC arriving
    MID-training can snapshot current weights (parity: worker SAVE_TO_FILE,
    include/distributed/worker.hpp:287-303, which the reference can service any
    time because its weights live in mutable host/device slabs).
    """
    log = get_logger("tnn.train")
    if config.log_file:
        # per-run file: replace sinks from previous runs, but leave caller-attached
        # sinks alone when this run doesn't request a file
        log.set_file_sink(config.log_file)
    profiler_mode = config.profiler_type.upper()
    profiling_on = profiler_mode not in ("", "NONE")
    cumulative_prof = profiler_mode == "CUMULATIVE"
    optimizer = optimizer or config.make_optimizer()
    scheduler = scheduler or config.make_scheduler()
    plateau = getattr(scheduler, "host_driven", False)

    batch_size = int(config.batch_size)
    sample_shape = tuple(train_loader.data_shape)
    input_shape = (batch_size,) + sample_shape
    rng = jax.random.PRNGKey(config.seed)

    # multi-chip: mesh_axes drives the parallel layout from config (parity:
    # the reference's mode/endpoint config, examples/tcp_coordinator.cpp:27-97):
    #   {"data": 8}                 -> DP, grads all-reduced by GSPMD
    #   {"data": 4, "fsdp": 2}      -> DP + ZeRO-style param sharding
    #   {"data": 2, "model": 4}     -> DP x Megatron TP (transformers)
    #   {"pipe": 4}                 -> compiled heterogeneous pipeline
    #   {"data": 2, "pipe": 4}      -> DP x PP in one program
    # (the reference offers data OR pipeline per run; its DP never all-reduces,
    # coordinator.hpp:37-40)
    axes = {k: int(v) for k, v in (config.mesh_axes or {}).items() if int(v) > 1}
    mesh = None
    place_batch = None
    pipe = None
    if "pipe" in axes:
        from .. import parallel
        from ..parallel import partitioner
        from ..parallel.pipeline import (make_pipeline_eval_step,
                                         make_pipeline_train_step)

        bad = set(axes) - {"pipe", "data"}
        if bad:
            raise ValueError(f"pipeline runs compose with 'data' only; got {axes}")
        pp, dp = axes["pipe"], axes.get("data", 1)
        if int(config.gradient_accumulation_steps) > 1:
            raise ValueError(
                "pipeline runs accumulate over num_microbatches; "
                "gradient_accumulation_steps > 1 would be silently ignored — "
                "set num_microbatches instead")
        num_mb = max(1, int(config.num_microbatches))
        if batch_size % (num_mb * dp):
            raise ValueError(f"batch_size {batch_size} not divisible by "
                             f"num_microbatches*data = {num_mb}*{dp}")
        mb_global = batch_size // num_mb
        mesh = parallel.make_mesh(data=dp, pipe=pp)
        virtual = max(1, int(getattr(config, "pipeline_virtual", 1)))
        stages = partitioner.partition_model(
            model, virtual * pp, (mb_global,) + sample_shape,
            strategy="balanced")
        io_dtype = jax.numpy.dtype(config.io_dtype)
        pipe, step_fn, init_fn = make_pipeline_train_step(
            stages, optimizer, mesh, (mb_global,) + sample_shape,
            loss_fn=config.loss, num_microbatches=num_mb,
            input_dtype=io_dtype, scheduler=scheduler,
            data_axis="data" if dp > 1 else None, augment=augment,
            remat=config.remat, virtual=virtual)
        if state is None:
            state = init_fn(rng)
        eval_fn = make_pipeline_eval_step(pipe)
        log.info("pipeline mesh %s: %d stages x %d microbatches (dp=%d, v=%d)",
                 dict(mesh.shape), virtual * pp, num_mb, dp, virtual)
    else:
        if state is None:
            state = create_train_state(model, optimizer, rng, input_shape)
        ring = None  # set by the seq branch; wraps eval too
        if axes:
            from .. import parallel

            unsupported = set(axes) - {"data", "fsdp", "model", "seq", "expert"}
            if unsupported:
                raise ValueError(
                    f"train_model auto-sharding handles data/fsdp/model/seq/"
                    f"expert/pipe axes; got {axes}.")
            shard_ways = axes.get("data", 1) * axes.get("fsdp", 1)
            if batch_size % shard_ways:
                raise ValueError(
                    f"batch_size {batch_size} not divisible by the "
                    f"data*fsdp mesh size {shard_ways} (mesh_axes={axes})")
            if axes.get("expert", 1) > 1:
                # same guard as the seq branch: an expert axis with nothing to
                # shard silently replicates all work N ways
                from jax.sharding import PartitionSpec as _P

                from ..nn.moe import ep_rules
                from ..parallel.tensor_parallel import spec_tree

                ep_specs = spec_tree(state.params, ep_rules())
                if all(s == _P() for s in jax.tree_util.tree_leaves(
                        ep_specs, is_leaf=lambda x: isinstance(x, _P))):
                    raise ValueError(
                        f"mesh_axes={{'expert': {axes['expert']}}} but the "
                        f"model has no MoE expert parameters — "
                        f"{axes['expert']}x devices would replicate work "
                        f"with zero speedup")
            mesh = parallel.make_mesh(
                **{k: axes.get(k, 1)
                   for k in ("data", "fsdp", "model", "seq", "expert")})
            step_fn, place_state, _place = parallel.make_dp_train_step(
                model, optimizer, mesh, loss_fn=config.loss, scheduler=scheduler,
                fsdp=axes.get("fsdp", 1) > 1, tp=axes.get("model", 1) > 1,
                ep=axes.get("expert", 1) > 1,
                grad_accum=config.gradient_accumulation_steps, augment=augment,
                remat=config.remat)
            if axes.get("seq", 1) > 1:
                # sequence/context parallelism: run steps inside a ring
                # context — every sdpa call becomes ring attention with K/V
                # rotating over ICI, with NO model mutation (checkpoints keep
                # their configured backend, decode works after training).
                # Beyond the reference, which has no sequence parallelism at
                # all (SURVEY.md preamble).
                from ..nn.attention import (count_attention_modules,
                                            ring_context)

                if count_attention_modules(model) == 0:
                    raise ValueError(
                        f"mesh_axes={{'seq': {axes['seq']}}} but the model has "
                        f"no attention modules — {axes['seq']}x devices would "
                        f"replicate work with zero speedup")
                if len(sample_shape) == 1 and sample_shape[0] % axes["seq"]:
                    raise ValueError(
                        f"sequence length {sample_shape[0]} not divisible by "
                        f"mesh_axes['seq'] = {axes['seq']}")
                batch_axes = tuple(a for a in ("data", "fsdp")
                                   if axes.get(a, 1) > 1)
                ring = ring_context(mesh, batch_axis=batch_axes or None,
                                    method=config.seq_parallel_method)
                base_step = step_fn

                def step_fn(state, data, labels, _f=base_step, _r=ring):
                    with _r:
                        return _f(state, data, labels)
            state = place_state(state)
            place_batch = lambda batch: _place(*batch)  # noqa: E731
            log.info("mesh %s: batch sharded over %d devices",
                     dict(mesh.shape), mesh.size)
        else:
            step_fn = make_train_step(
                model, optimizer, loss_fn=config.loss, scheduler=scheduler,
                grad_accum=config.gradient_accumulation_steps, augment=augment,
                remat=config.remat)
        base_eval = make_eval_step(model, loss_fn=config.loss)
        if mesh is not None:
            def eval_fn(state, data, labels, _f=base_eval, _m=mesh, _r=ring):
                if _r is not None:
                    with _m, _r:
                        return _f(state, data, labels)
                with _m:
                    return _f(state, data, labels)
        else:
            eval_fn = base_eval

    ckpt = Checkpoint(config.snapshot_dir)
    best_val = -float("inf")
    resumed = False
    if config.resume:
        state, meta = Checkpoint(config.resume).restore(
            state, scheduler=scheduler, loader=train_loader)
        best_val = float(meta.get("extra", {}).get("best_val", -float("inf")))
        resumed = True
        log.info("resumed from %s at step %d", config.resume, int(state.step))
        # restore loads host arrays with no sharding — re-apply the layout or
        # a resumed FSDP/TP/pipeline run silently trains fully replicated
        if pipe is not None:
            state = pipe.place_train_state(state)
        elif mesh is not None:
            state = place_state(state)

    history: List[Dict[str, Any]] = []
    if state_hook:
        state_hook(state)
    if config.shuffle and not resumed:
        train_loader.shuffle()

    # profiler state is touched ONLY when this run asked for profiling (a NONE run
    # never clobbers a caller's own enable()/events), and only right before the
    # try whose finally restores it — no leak on early setup failures
    if profiling_on:
        GlobalProfiler.clear()
        _prof_mod.enable(True)
    try:
        for epoch in range(int(config.epochs)):
            t_epoch = time.perf_counter()
            window_t0 = time.perf_counter()
            n_batches = 0
            m: Dict[str, Any] = {}

            # a resumed first epoch continues mid-epoch from the restored cursor/order
            # (an end-of-epoch checkpoint has no batches left -> start a fresh epoch)
            continue_epoch = (resumed and epoch == 0
                              and train_loader.remaining_batches(batch_size) > 0)
            for data, labels in _staged_batches(train_loader, batch_size, config,
                                                reset=not continue_epoch,
                                                limit=config.max_steps,
                                                place=place_batch):
                # host-side span = dispatch of one compiled step (device runs async; record
                # with jax.profiler.start_trace for per-HLO timing). CUMULATIVE keeps only
                # constant-memory counters; NORMAL records one event per step.
                if cumulative_prof:
                    t_step = time.perf_counter()
                    state, m = step_fn(state, data, labels)
                    GlobalProfiler.tick("train_step", time.perf_counter() - t_step)
                else:
                    with profiled(f"train_step/epoch{epoch}", EventType.COMPUTE):
                        state, m = step_fn(state, data, labels)
                n_batches += 1
                # async: pull metrics only at print interval so the device never waits
                if n_batches % max(1, config.progress_print_interval) == 0:
                    loss = float(m["loss"])
                    acc = float(m.get("accuracy", 0.0))
                    dt_batch = (time.perf_counter() - window_t0) * 1e3 / max(
                        1, config.progress_print_interval)
                    window_t0 = time.perf_counter()
                    log.info(
                        "epoch %d batch %d: loss=%.4f acc=%.4f %.1f ms/batch (%.0f samples/s)",
                        epoch, n_batches, loss, acc, dt_batch,
                        batch_size * 1e3 / max(dt_batch, 1e-9))
                    if config.print_memory_usage:
                        log.info("host RSS: %.1f MiB", memory_usage_kb() / 1024)
                    if metric_hook:
                        metric_hook(int(state.step),
                                    {"loss": loss, "accuracy": acc, "epoch": epoch})
                    if state_hook:
                        state_hook(state)

            if state_hook:
                state_hook(state)
            # final metric of the epoch (forces one sync)
            epoch_metrics: Dict[str, Any] = {
                "epoch": epoch,
                "train_loss": float(m["loss"]) if n_batches else float("nan"),
                "train_accuracy": float(m.get("accuracy", 0.0)) if n_batches else 0.0,
                "batches": n_batches,
                "epoch_seconds": time.perf_counter() - t_epoch,
            }

            if val_loader is not None:
                val = evaluate(eval_fn, state, val_loader, batch_size, config,
                               place=place_batch)
                epoch_metrics["val_loss"] = val["loss"]
                epoch_metrics["val_accuracy"] = val.get("accuracy", 0.0)
                if plateau and np.isfinite(val["loss"]):
                    scheduler.observe(val["loss"])
                score = val.get("accuracy", -val["loss"])
                if score > best_val:
                    best_val = score
                    path = ckpt.save(state, model=model, scheduler=scheduler,
                                     loader=train_loader,
                                     extra={"epoch": epoch, **val}, best=True)
                    log.info("new best val %.4f -> %s", score, path)

            # per-epoch snapshot overlaps its disk write with the next epoch
            # (block=False); best-val saves above stay blocking — their path
            # is logged and may be read back immediately
            ckpt.save(state, model=model, scheduler=scheduler, loader=train_loader,
                      extra={**epoch_metrics, "best_val": best_val}, block=False)
            log.info(
                "epoch %d done in %.1fs: train loss=%.4f acc=%.4f%s", epoch,
                epoch_metrics["epoch_seconds"], epoch_metrics["train_loss"],
                epoch_metrics["train_accuracy"],
                (f" | val loss={epoch_metrics['val_loss']:.4f} "
                 f"acc={epoch_metrics.get('val_accuracy', 0):.4f}")
                if val_loader is not None else "")
            history.append(epoch_metrics)
    finally:
        ckpt.wait()  # the last epoch's async snapshot must land before return
        if profiling_on:
            for name, s in sorted(GlobalProfiler.summary().items()):
                log.info("profile %s: n=%d total=%.3fs mean=%.1fms", name,
                         int(s["count"]), s["total_s"], s["mean_s"] * 1e3)
            for key, total in sorted(GlobalProfiler.counters.items()):
                log.info("profile counter %s: total=%.3fs", key, total)
            _prof_mod.enable(False)

    return state, history
