#!/usr/bin/env python
"""Serving front end: stdin/stdout JSON lines, or HTTP/SSE with --http.

    echo '{"prompt": "The meaning of life is", "max_new_tokens": 16}' | \
        python -m tnn_tpu.cli.serve --model gpt2_small

    python -m tnn_tpu.cli.serve --model gpt2_small --http 127.0.0.1:8100

Both front ends are thin clients of the same supervised runtime
(``serving.EngineSupervisor``): the engine steps on a worker thread behind
a thread-safe command queue, wrapped with crash recovery (bounded restart
budget + exponential backoff), an optional step-latency watchdog, and
graceful drain. SIGINT/SIGTERM — and EOF on stdin — trigger the drain:
admissions close, in-flight requests finish (or deadline out after
--drain-deadline-s), every event is flushed, and the process exits 0.

Each stdin line is one request:

    {"id": 3, "prompt": "text", "max_new_tokens": 32,
     "temperature": 0.8, "top_k": 40, "top_p": 0.9,
     "deadline_s": 30.0, "max_queue_s": 5.0, "priority": 1}
    {"id": 4, "tokens": [464, 3616, 286], "max_new_tokens": 8}
    {"op": "cancel", "id": 4}

``tokens`` bypasses tokenization; ``prompt`` text uses --vocab (reference
vocab.bin) when given, else byte-level ids. ``id`` defaults to the engine
request id. ``priority`` (smaller = more important) controls load shedding
under --max-queue-depth backpressure. ``op: cancel`` aborts a queued or
running request by its user id.

Responses stream as the engine produces them, one JSON object per line:

    {"event": "token", "id": 3, "token": 257}
    {"event": "done", "id": 3, "tokens": [...], "text": "...",
     "finish_reason": "length", "ttft_ms": 12.3}
    {"event": "error", "id": 3, "reason": "..."}       (failed / rejected)
    {"event": "timeout", "id": 3, "reason": "..."}     (deadline expired)
    {"event": "cancelled", "id": 3, "reason": "..."}

The server process is fault-tolerant by construction: a bad JSON line or a
rejected submit emits a structured event and the loop keeps serving, and a
crash of the engine loop itself is caught by the supervisor, which fails
the in-flight requests with structured errors, resets the KV pool, and
keeps serving the queue (see docs/serving.md's Operations section).
"""
import argparse
import json
import os
import queue
import select
import signal
import sys
import time


import jax
import numpy as np

from tnn_tpu import checkpoint as ckpt_lib
from tnn_tpu import models
from tnn_tpu.data.tokenizer import Tokenizer
from tnn_tpu.profiling.profiler import Profiler, span
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving import (AdmissionRejected, EngineSupervisor,
                             InferenceEngine, Router, ShuttingDown,
                             run_server)
from tnn_tpu.utils import compile_cache
from tnn_tpu.utils.hardware import device_line


from tnn_tpu.cli import console_entry


def _emit(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


POLL_S = 0.05       # one poll of stdin by the JSON-lines front end


def _read_stdin_lines(fd: int, pending: bytes, timeout: float):
    """Lines that arrived on ``fd`` within ``timeout``: (lines, pending, eof).

    Reads the descriptor itself. A buffered ``readline()`` pulls every line
    the client has sent so far into Python's buffer, where ``select`` cannot
    see them: a client that wrote two requests at once would have its second
    one sit there until it wrote again."""
    if not select.select([fd], [], [], timeout)[0]:
        return [], pending, False
    chunk = os.read(fd, 1 << 16)
    if not chunk:           # EOF: an unterminated last line still counts
        return ([pending] if pending else []), b"", True
    *lines, pending = (pending + chunk).split(b"\n")
    return lines, pending, False


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="gpt2_small",
                    help="zoo name (used when --model-file is absent)")
    ap.add_argument("--model-file", default="", help=".tnn snapshot")
    ap.add_argument("--vocab", default="", help="vocab.bin (reference format)")
    ap.add_argument("--http", default="",
                    help="serve HTTP+SSE on HOST:PORT instead of stdin "
                         "JSON lines (e.g. 127.0.0.1:8100)")
    ap.add_argument("--num-blocks", type=int, default=64,
                    help="KV pool size in blocks (1 is reserved scratch)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block")
    ap.add_argument("--max-batch-size", type=int, default=8,
                    help="decode batch width (one compile at this width)")
    ap.add_argument("--chunk-size", type=int, default=64,
                    help="prompt tokens per mixed-step prefill chunk "
                         "(chunked prefill co-schedules prompt chunks with "
                         "decode rows in one compiled step)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable automatic prefix caching (content-"
                         "addressed KV block reuse across requests)")
    ap.add_argument("--prefix-cache-min-hit-blocks", type=int, default=1,
                    help="ignore prefix-cache matches shorter than this "
                         "many full KV blocks")
    ap.add_argument("--max-seq-len", type=int, default=0,
                    help="per-request position cap (0 = model/pool limit)")
    ap.add_argument("--kv-dtype", default="f32", choices=("f32", "int8"),
                    help="KV pool page dtype: int8 halves resident KV and "
                         "decode page traffic (per-row f32 scale sidecar; "
                         "output gated by closeness, not exactness)")
    ap.add_argument("--quant-weights", action="store_true",
                    help="serve projection/MLP matmuls from int8 weights "
                         "via the in-VMEM-dequant quant_matmul kernel")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard attention heads and "
                         "the paged KV pool head-wise over this many chips "
                         "(one all-reduce per layer for attention out + MLP; "
                         "requires num_kv_heads %% tp == 0 and tp <= "
                         "device count; token-exact vs tp=1)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel degree: shard each request's KV "
                         "blocks position-wise over a context mesh of this "
                         "many chips, so one prompt's cache can exceed a "
                         "single chip's pool (aggregate capacity ~ N x). "
                         "Every shard sweeps its own pages with the ragged "
                         "paged kernel and partial attention merges via one "
                         "online-softmax psum per layer; token-exact vs "
                         "sp=1. Requires sp <= device count; pick ONE of "
                         "--sp / --tp per replica")
    ap.add_argument("--compile-cache", default="",
                    help="persistent XLA compilation cache directory "
                         "(default <checkout>/.jax_cache; ignored when "
                         "JAX_COMPILATION_CACHE_DIR is set): step "
                         "programs compiled on a previous run (or by a "
                         "sibling replica on shared storage) are reloaded "
                         "instead of recompiled, cutting restart and "
                         "scale-up cold time; content-addressed, so a "
                         "changed jaxlib or flag set misses cleanly")
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="host-RAM KV tier capacity in bytes (0 = off): "
                         "prefix-cache blocks the pool would reclaim are "
                         "demoted to host memory and re-admitted on a later "
                         "prefix hit after a rolling-hash digest check (a "
                         "corrupt or torn block degrades to an uncached "
                         "miss, never wrong KV); requires the prefix cache, "
                         "incompatible with --tp > 1")
    ap.add_argument("--autoscale", default="",
                    help="MIN:MAX — run a load-driven autoscaler over the "
                         "replica fleet: scale up under queue pressure via "
                         "the router join path, scale down after a "
                         "hysteresis-guarded quiet period by draining the "
                         "least-loaded replica with its live streams "
                         "proactively migrated token-exact (zero dropped "
                         "requests); --replicas sets the starting size "
                         "(clamped into [MIN, MAX])")
    ap.add_argument("--max-new-tokens", type=int, default=32,
                    help="default for requests that omit it")
    ap.add_argument("--max-queue-depth", type=int, default=0,
                    help="bounded admission: reject submits past this many "
                         "waiting requests (0 = unbounded); priority-aware "
                         "shedding displaces less-important queued work")
    ap.add_argument("--preemption-budget", type=int, default=16,
                    help="recompute preemptions a request may absorb before "
                         "it fails cleanly (-1 = unlimited)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="default per-request wall deadline (0 = none)")
    ap.add_argument("--watchdog-s", type=float, default=0.0,
                    help="step-latency watchdog: a step exceeding this wall "
                         "time restarts the engine (0 = off; set above "
                         "worst-case compile time — cold steps compile)")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="engine crash/watchdog recoveries before the "
                         "supervisor gives up and fails all requests")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run N supervised engine replicas behind a failover "
                         "router: join-shortest-queue placement, per-replica "
                         "circuit breakers, bounded retries, and token-exact "
                         "mid-stream migration when a replica dies")
    ap.add_argument("--migration-budget", type=int, default=3,
                    help="crash migrations one request may absorb — engine "
                         "restart re-admissions and router failovers each "
                         "spend from their own budget of this size — before "
                         "it fails as poison (-1 = unlimited)")
    ap.add_argument("--hedge-ttft-s", type=float, default=-1.0,
                    help="router hedging: duplicate a request onto the "
                         "next-best replica when its first token is this "
                         "late (-1 = adaptive, the fleet's rolling TTFT "
                         "p95). First token wins; the loser is cancelled "
                         "and never charges a breaker")
    ap.add_argument("--hedge-budget", type=float, default=0.1,
                    help="max concurrent hedges as a fraction of open "
                         "requests, consulted before every fire "
                         "(0 = hedging off)")
    ap.add_argument("--degrade-factor", type=float, default=2.0,
                    help="eject a replica from placement as DEGRADED when "
                         "its health score stays worse than this multiple "
                         "of the fleet median (0 = ejection off); its live "
                         "streams proactively migrate token-exact")
    ap.add_argument("--roles", default="",
                    help="disaggregated prefill/decode serving: comma-"
                         "separated per-replica roles (prefill|decode|mixed) "
                         "matching the fleet size, or 'auto' to let the "
                         "router rank replicas by health score and dedicate "
                         "the healthiest half to decode. Roles are placement "
                         "preferences — no request ever fails for lack of a "
                         "matching role")
    ap.add_argument("--disagg-prompt-threshold", type=int, default=128,
                    help="with --roles: prompts at least this many tokens "
                         "long prefer a prefill replica; the router hands "
                         "the stream to a decode replica at the first-token "
                         "boundary (token-exact)")
    ap.add_argument("--no-handoff-kv", action="store_true",
                    help="disable the KV-block handoff at the prefill/"
                         "decode boundary — the stream still moves, via "
                         "token-exact recompute-resume (A/B baseline)")
    ap.add_argument("--fleet-prefix", action="store_true",
                    help="fleet-wide shared prefix cache: the router tracks "
                         "which replica holds which prefix chain keys and "
                         "pulls blocks from a peer on a local miss through "
                         "the digest-verified export/adopt path (a failed "
                         "pull is just a cache miss, never wrong KV)")
    ap.add_argument("--drain-deadline-s", type=float, default=30.0,
                    help="graceful-drain budget: in-flight work past this "
                         "deadline times out (0 = wait forever)")
    ap.add_argument("--no-logit-guard", action="store_true",
                    help="disable per-row non-finite logit detection")
    ap.add_argument("--no-overlap", action="store_true",
                    help="escape hatch: run the fully synchronous engine "
                         "loop (one blocking fetch per step, no step kept "
                         "in flight during host bookkeeping)")
    ap.add_argument("--spec", default="off",
                    choices=("off", "ngram", "draft"),
                    help="speculative decoding: 'ngram' self-drafts from the "
                         "request's own context, 'draft' scores lookahead "
                         "with a tiny zoo draft model (gpt2_tiny, random "
                         "weights unless it shares the target checkpoint's "
                         "vocab). Greedy output is token-exact either way")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max drafted tokens verified per decode row per "
                         "step (the mixed step widens to k+1)")
    ap.add_argument("--trace", default="",
                    help="enable request-scoped tracing and write one merged "
                         "Chrome/Perfetto trace (router + every replica on "
                         "its own track) to this path on exit")
    ap.add_argument("--flight-dir", default="",
                    help="directory for crash flight-recorder JSONL dumps; "
                         "each supervisor dumps its last-N step records on "
                         "crash, watchdog trip, restart-budget exhaustion, "
                         "kill, and drain")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # fail fast on impossible elastic-fleet configs BEFORE touching model
    # weights: the engine/autoscaler constructors would reject them anyway,
    # but a clear one-line error beats a traceback out of engine wiring
    autoscale = None
    if args.autoscale:
        lo, sep, hi = args.autoscale.partition(":")
        try:
            autoscale = (int(lo), int(hi))
        except ValueError:
            autoscale = None
        if not sep or autoscale is None:
            ap.error(f"--autoscale {args.autoscale!r} is not MIN:MAX "
                     "(two integers, e.g. --autoscale 1:4)")
        if autoscale[0] < 1:
            ap.error(f"--autoscale MIN must be >= 1, got {autoscale[0]}")
        if autoscale[1] < autoscale[0]:
            ap.error(f"--autoscale MAX ({autoscale[1]}) must be >= MIN "
                     f"({autoscale[0]})")
    if args.host_tier_bytes < 0:
        ap.error(f"--host-tier-bytes must be >= 0, got "
                 f"{args.host_tier_bytes}")
    if args.host_tier_bytes:
        if args.no_prefix_cache:
            ap.error("--host-tier-bytes needs the prefix cache (the tier "
                     "is keyed by its rolling-hash chain) — drop "
                     "--no-prefix-cache or the tier")
        if args.tp > 1:
            ap.error("--host-tier-bytes is incompatible with --tp > 1 "
                     "(demoted page slices would need a cross-shard "
                     "gather/scatter)")
    roles = None
    if args.roles:
        if args.replicas <= 1 and not args.autoscale:
            ap.error("--roles needs a replica fleet "
                     "(--replicas N > 1 or --autoscale MIN:MAX)")
        if args.roles == "auto":
            roles = "auto"
        else:
            roles = [r.strip() for r in args.roles.split(",")]
            bad = sorted(set(r for r in roles
                             if r not in ("prefill", "decode", "mixed")))
            if bad:
                ap.error(f"--roles: unknown role(s) {', '.join(bad)} "
                         "(choose prefill, decode, or mixed)")
            if "prefill" in roles and not any(
                    r in ("decode", "mixed") for r in roles):
                ap.error("--roles: a disaggregated fleet needs at least "
                         "one decode or mixed replica")
    if args.disagg_prompt_threshold < 1:
        ap.error(f"--disagg-prompt-threshold must be >= 1, got "
                 f"{args.disagg_prompt_threshold}")
    if args.fleet_prefix and args.no_prefix_cache:
        ap.error("--fleet-prefix needs the prefix cache (pulled blocks "
                 "are keyed by its rolling-hash chain) — drop "
                 "--no-prefix-cache")

    tokenizer = None
    if args.vocab:
        tokenizer = Tokenizer().load(args.vocab)

    if args.model_file:
        model, variables = ckpt_lib.load_model(args.model_file)
        params = variables["params"]
    else:
        model = models.create(args.model)
        # params init deferred until the mesh pre-flights below pass:
        # building random gpt2_small weights takes seconds, and a config
        # error should die before that, not after
        params = None

    # a model whose state is not K/V blocks of heads (EVA's window and
    # summaries, latent rows) says in one sentence what it does not serve
    # with, before any weights are made
    refusal = refuse_windowed(
        model, prefix_cache=not args.no_prefix_cache,
        spec=args.spec != "off", tp=args.tp, sp=args.sp,
        host_tier_bytes=args.host_tier_bytes, kv_dtype=args.kv_dtype)
    if refusal:
        ap.error(refusal)

    # fail fast on an impossible TP config BEFORE touching model weights:
    # the engine would reject it anyway, but a clear one-line error beats
    # a traceback out of shard placement
    if args.tp > 1:
        n_dev = jax.device_count()
        if args.tp > n_dev:
            ap.error(f"--tp {args.tp} exceeds the {n_dev} visible "
                     "device(s); off-TPU, raise the host device count with "
                     "--xla_force_host_platform_device_count in XLA_FLAGS")
        h_kv = getattr(model, "num_kv_heads", model.num_heads)
        if h_kv % args.tp:
            ap.error(f"--tp {args.tp} does not divide the model's "
                     f"{h_kv} KV head(s); head-sharded TP needs "
                     "num_kv_heads % tp == 0")
        if args.quant_weights:
            ap.error("--quant-weights is incompatible with --tp > 1 "
                     "(int8 weight leaves don't column-shard)")

    # same fail-fast treatment for an impossible SP (context mesh) config
    if args.sp > 1:
        n_dev = jax.device_count()
        if args.sp > n_dev:
            ap.error(f"--sp {args.sp} exceeds the {n_dev} visible "
                     "device(s); off-TPU, raise the host device count with "
                     "--xla_force_host_platform_device_count in XLA_FLAGS")
        if args.tp > 1:
            ap.error(f"--sp {args.sp} with --tp {args.tp} is unsupported "
                     "this engine — the context mesh and the head mesh "
                     "would need a 2-D shard_map; pick ONE of --sp / --tp "
                     "per replica")
        if args.host_tier_bytes:
            ap.error("--host-tier-bytes is incompatible with --sp > 1 "
                     "(a demoted block's pages live on one context-mesh "
                     "shard; run the host tier on single-chip replicas)")
        if args.num_blocks % args.sp:
            ap.error(f"--num-blocks {args.num_blocks} does not divide "
                     f"evenly over --sp {args.sp} shards")
        if args.quant_weights:
            ap.error("--quant-weights is incompatible with --sp > 1 "
                     "(int8 weight leaves re-materialize off-mesh)")
        # mirror the engine's assembly-width computation so a bad
        # max_seq_len dies here as a one-liner, not a ctor traceback
        cap = min(model.max_len, (args.num_blocks - args.sp)
                  * args.block_size)
        msl = min(args.max_seq_len or cap, cap)
        nb = -(-msl // args.block_size)
        if nb % args.sp:
            ap.error(f"--sp {args.sp} does not divide the assembly width "
                     f"({nb} blocks/seq from max_seq_len {msl}, block "
                     f"size {args.block_size}); pick --max-seq-len (or "
                     "--num-blocks/--block-size) so ceil(max_seq_len / "
                     "block_size) is a multiple of sp")

    # before the first compile (the weight init below is one)
    cache_dir = compile_cache.enable(args.compile_cache or None)
    print(f"compile cache: {compile_cache.describe(cache_dir)}",
          file=sys.stderr)

    if params is None:
        print(f"no --model-file: random-weight {args.model} "
              "(smoke/benchmark mode)", file=sys.stderr)
        params = model.init(jax.random.PRNGKey(args.seed), (1, 8))["params"]

    draft_model = draft_params = None
    if args.spec == "draft":
        draft_model = models.create("gpt2_tiny", vocab_size=model.vocab_size,
                                    max_len=model.max_len)
        draft_params = draft_model.init(
            jax.random.PRNGKey(args.seed + 1), (1, 8))["params"]
        print("spec=draft: random-weight gpt2_tiny drafter (wire a trained "
              "draft checkpoint for real acceptance rates)", file=sys.stderr)

    profilers = []

    # a fleet of single-chip replicas spreads over the chips: replica i on
    # local device i (wrapping when there are more replicas than chips). A
    # tp/sp replica spans its own mesh, and one device leaves nothing to place
    devices = jax.local_devices()
    spread = (args.replicas > 1 or autoscale is not None) \
        and args.tp == 1 and args.sp == 1 and len(devices) > 1

    def build_engine(idx=0):
        prof = None
        if args.trace:
            prof = Profiler(source=f"replica{idx}")
            profilers.append(prof)
        return InferenceEngine(
            model, params, num_blocks=args.num_blocks,
            block_size=args.block_size,
            max_batch_size=args.max_batch_size, chunk_size=args.chunk_size,
            prefix_cache=not args.no_prefix_cache,
            prefix_cache_min_hit_blocks=args.prefix_cache_min_hit_blocks,
            max_seq_len=args.max_seq_len or None,
            max_queue_depth=args.max_queue_depth,
            preemption_budget=(None if args.preemption_budget < 0
                               else args.preemption_budget),
            migration_budget=(None if args.migration_budget < 0
                              else args.migration_budget),
            logit_guard=not args.no_logit_guard,
            spec=args.spec, spec_k=args.spec_k,
            draft_model=draft_model, draft_params=draft_params,
            profiler=prof, trace=bool(args.trace),
            overlap=not args.no_overlap,
            kv_dtype=args.kv_dtype, quant_weights=args.quant_weights,
            tp=args.tp, sp=args.sp, host_tier_bytes=args.host_tier_bytes,
            seed=args.seed,
            device=devices[idx % len(devices)] if spread else None)

    def build_supervisor(eng, idx=0):
        # each replica dumps into its own subdirectory so the per-reason
        # sequence numbers of different replicas never collide
        flight_dir = (os.path.join(args.flight_dir, f"replica{idx}")
                      if args.flight_dir else None)
        return EngineSupervisor(
            eng, watchdog_step_s=args.watchdog_s or None,
            max_restarts=args.max_restarts,
            drain_deadline_s=args.drain_deadline_s or None,
            flight_dir=flight_dir)

    engine = build_engine()
    # one line saying where this process really runs: a server that landed
    # on the CPU or interprets its kernels must not look like one that did not
    print(f"tnn-serve: {device_line()}", file=sys.stderr)
    if args.host_tier_bytes:
        print(f"host KV tier: {args.host_tier_bytes} bytes, verified "
              "re-admission (corrupt blocks degrade to uncached misses)",
              file=sys.stderr)
    if args.tp > 1:
        print(f"tensor parallel: tp={args.tp}, "
              f"{model.num_heads // args.tp} head(s)/shard, per-shard KV "
              f"{engine.stats()['kv_bytes_per_token_per_shard']} B/token",
              file=sys.stderr)
    if args.sp > 1:
        print(f"sequence parallel: sp={args.sp}, "
              f"{engine.pool.blocks_per_shard} block(s)/shard, max context "
              f"{engine.max_seq_len} tokens over the context mesh",
              file=sys.stderr)

    scaler = None
    if args.replicas > 1 or autoscale is not None:
        # replicas share read-only params; each gets its own KV pool,
        # scheduler, and supervised worker thread. With --autoscale the
        # router starts at the clamped --replicas size and the controller
        # grows/shrinks it between MIN and MAX
        n0 = args.replicas
        if autoscale is not None:
            n0 = min(max(args.replicas, autoscale[0]), autoscale[1])
        if isinstance(roles, list) and len(roles) != n0:
            ap.error(f"--roles names {len(roles)} replica(s) but the "
                     f"fleet starts at {n0} — give one role per replica "
                     "or use --roles auto")
        sups = [build_supervisor(engine)] + [
            build_supervisor(build_engine(i), i)
            for i in range(1, n0)]
        router_prof = Profiler(source="router") if args.trace else None
        supervisor = Router(
            sups,
            migration_budget=(10 ** 9 if args.migration_budget < 0
                              else args.migration_budget),
            hedge_ttft_s=(None if args.hedge_ttft_s < 0
                          else args.hedge_ttft_s),
            hedge_budget=args.hedge_budget,
            degrade_factor=args.degrade_factor,
            roles=roles,
            disagg_prompt_threshold=args.disagg_prompt_threshold,
            handoff_kv=not args.no_handoff_kv,
            fleet_prefix=args.fleet_prefix,
            seed=args.seed, profiler=router_prof)
        print(f"router: {n0} supervised replicas"
              + (f", one per device over {len(devices)} devices" if spread
                 else ""), file=sys.stderr)
        if roles is not None:
            kv = "recompute-resume only" if args.no_handoff_kv \
                else "verified KV-block handoff"
            print(f"disaggregated serving: roles="
                  f"{roles if roles == 'auto' else ','.join(roles)}, "
                  f"prompt threshold {args.disagg_prompt_threshold}, {kv}",
                  file=sys.stderr)
        if args.fleet_prefix:
            print("fleet prefix cache: content-addressed directory + "
                  "peer block pulls (verified)", file=sys.stderr)
        if autoscale is not None:
            from tnn_tpu.serving import Autoscaler

            next_idx = [n0]

            def scale_factory():
                idx = next_idx[0]
                next_idx[0] += 1
                return build_supervisor(build_engine(idx), idx)

            scaler = Autoscaler(supervisor, scale_factory,
                                min_replicas=autoscale[0],
                                max_replicas=autoscale[1]).start()
            print(f"autoscaler: {autoscale[0]}..{autoscale[1]} replicas, "
                  "zero-loss scale-down (live streams migrate token-exact "
                  "before a replica drains)", file=sys.stderr)
    else:
        router_prof = None
        supervisor = build_supervisor(engine)

    def dump_trace():
        if not args.trace:
            return
        # one merged Perfetto view: router spans plus every replica's
        # engine spans, each source on its own track
        merged = router_prof if router_prof is not None else Profiler(
            source="router")
        for prof in profilers:
            merged.merge(prof)
        merged.to_chrome_trace(args.trace)
        print(f"trace written to {args.trace}", file=sys.stderr)

    try:
        if args.http:
            host, _, port = args.http.rpartition(":")
            code = run_server(supervisor, host=host or "127.0.0.1",
                              port=int(port), tokenizer=tokenizer,
                              default_max_new=args.max_new_tokens)
            supervisor.join(10.0)  # let worker threads exit before teardown
            dump_trace()
            _print_summary(supervisor)
            return code
        code = _serve_stdin(supervisor, model, tokenizer, args)
        dump_trace()
        return code
    finally:
        if scaler is not None:
            scaler.stop()


def _serve_stdin(supervisor, model, tokenizer, args):
    """Stdin JSON-lines loop as a thin client of the supervisor: requests
    marshal onto the worker thread, events flow back through the sink
    queue, and SIGINT/SIGTERM/EOF all converge on one graceful drain.

    The loop's phases are spans on the JAX profiler's clock (``front.read``
    the stdin poll, ``front.submit`` one request line, ``front.flush`` the
    events written out), and every ``token`` event is stamped when the
    worker hands it over, right after its step's commit, so a flush can
    record how long it waited (``observe_emit_delay``). A poll of stdin
    that comes back EMPTY slept in ``select`` for ``POLL_S`` and touched no
    device: what it took beyond that, the machine took from every thread
    (``observe_front_late``; long at the same instant as a step is the
    machine, a long step alone is the device or the runtime)."""
    out_q: "queue.Queue" = queue.Queue()
    supervisor.event_sink = lambda ev: out_q.put((time.perf_counter(), ev))

    ids_by_rid = {}
    rid_by_user = {}

    def handle_line(line: str):
        """One client line: submit or cancel. Emits structured error events
        instead of raising — a bad line must never kill the server."""
        try:
            req = json.loads(line)
        except json.JSONDecodeError as e:
            _emit({"event": "error", "reason": f"bad json: {e}"})
            return
        with span("front.submit", rid=req.get("id")):
            submit(req)

    def submit(req):
        if req.get("op") == "cancel":
            user_id = req.get("id")
            rid = rid_by_user.get(user_id)
            if rid is None or not supervisor.cancel(rid):
                _emit({"event": "error", "id": user_id,
                       "reason": "cancel: unknown or already-terminal id"})
            return  # on success the sweep emits the cancelled event
        try:
            if "tokens" in req:
                ids = np.asarray(req["tokens"], np.int32)
            elif tokenizer is not None:
                ids = np.asarray(tokenizer.encode(req["prompt"]), np.int32)
            else:
                ids = np.frombuffer(req["prompt"].encode(), np.uint8).astype(
                    np.int32) % model.vocab_size
            deadline = req.get("deadline_s", args.deadline_s or None)
            rid = supervisor.submit(
                ids, int(req.get("max_new_tokens", args.max_new_tokens)),
                temperature=float(req.get("temperature", 0.0)),
                top_k=int(req.get("top_k", 0)),
                top_p=float(req.get("top_p", 0.0)),
                stop_token=req.get("stop_token"),
                deadline_s=(float(deadline) if deadline else None),
                max_queue_s=(float(req["max_queue_s"])
                             if req.get("max_queue_s") else None),
                priority=int(req.get("priority", 0)))
        except AdmissionRejected as e:
            _emit({"event": "error", "id": req.get("id"),
                   "reason": str(e), "rejected": True})
            return
        except ShuttingDown as e:
            _emit({"event": "error", "id": req.get("id"),
                   "reason": str(e), "draining": True})
            return
        except (ValueError, KeyError, TypeError) as e:
            _emit({"event": "error", "id": req.get("id"), "reason": str(e)})
            return
        user_id = req.get("id", rid)
        ids_by_rid[rid] = user_id
        rid_by_user[user_id] = rid

    def emit_event(ev):
        out = dict(ev)
        rid = out.get("id")
        out["id"] = ids_by_rid.get(rid, rid)
        if ev.get("event") == "done" and tokenizer is not None:
            out["text"] = tokenizer.decode(ev["tokens"])
        _emit(out)

    def current_metrics():
        # the engine's CURRENT registry, looked up at every use: a caller
        # may swap it to mark a window (never one captured at start-up)
        return getattr(supervisor, "engine", supervisor).metrics

    def flush_events():
        if out_q.empty():
            return
        metrics = current_metrics()
        n = 0
        with span("front.flush") as flush:
            while True:
                try:
                    t_commit, ev = out_q.get_nowait()
                except queue.Empty:
                    break
                emit_event(ev)
                n += 1
                if ev.get("event") == "token":
                    metrics.observe_emit_delay(
                        time.perf_counter() - t_commit)
            flush.set_metadata(n=n)

    supervisor.start()
    old_handlers = {}
    try:
        for signum in (signal.SIGINT, signal.SIGTERM):
            old_handlers[signum] = signal.signal(
                signum, lambda s, f: supervisor.request_drain(
                    f"{signal.Signals(s).name} received"))
    except ValueError:
        pass  # not the main thread (embedded use): signals stay external

    try:
        fd, pending, eof = sys.stdin.fileno(), b"", False
        while not supervisor.finished:
            flush_events()
            if eof or supervisor.draining:
                supervisor.join(0.05)  # drain in progress: just wait
                continue
            t_poll = time.perf_counter()
            with span("front.read"):
                lines, pending, eof = _read_stdin_lines(fd, pending, POLL_S)
            if not lines and not eof:
                current_metrics().observe_front_late(
                    max(0.0, time.perf_counter() - t_poll - POLL_S))
            for raw in lines:
                if raw.strip():
                    handle_line(raw.decode(errors="replace"))
            if eof:
                # EOF drains: in-flight work finishes instead of being
                # dropped on the floor by a process exit
                supervisor.request_drain("stdin EOF")
        flush_events()
        # finished flips before the worker threads (replicas + router
        # monitor) run their last instructions; exiting the interpreter
        # under a daemon thread still inside its final jitted call aborts
        # in native XLA teardown. Bounded join before we let Python die.
        supervisor.join(10.0)
    finally:
        for signum, handler in old_handlers.items():
            signal.signal(signum, handler)

    _print_summary(supervisor)
    return supervisor.exit_code if supervisor.exit_code is not None else 0


def _print_summary(supervisor):
    summary = supervisor.stats()
    print("serve summary: " + json.dumps(
        {k: round(v, 3) if isinstance(v, float) else v
         for k, v in summary.items()}), file=sys.stderr)


cli = console_entry(main)


if __name__ == "__main__":
    sys.exit(main() or 0)
