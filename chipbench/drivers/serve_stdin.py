"""Driver ``serve_stdin``: ``tnn-serve`` (``tnn_tpu.cli.serve.main``) in this
process, as a user runs it: requests go in as JSON lines on stdin (a pipe fed
by the client thread), events come back on stdout, the summary on stderr.
The program runs in the main thread; the traffic generator runs the client.

The only things put under the program are (1) the weights, which the
benchmark makes from the seed and the program takes in place of its own
random initialisation, and (2) a subclass of ``InferenceEngine`` that only
remembers the engine, so that the window can be marked on its counters.
(Copied in shape from ``chip_smoke.drive_serve``, proven on the chip in PR 21.)

The driver names no family of models. Everything it knows of the model comes
from the configuration's reference module (``chipbench/reference/<family>.py``):
``sizes_of(config) -> sz`` (whatever the family needs, handed on to the
reference's ``Forward`` and to every ``opcount``), ``make_params(sz, seed)``
(in the type the program keeps its weights in) and ``check_program(model, sz,
name)`` (refuses a program whose model is not the configuration's). The
harness itself reads two sizes, each under one name: ``sz["vocab_size"]``,
which ids are drawn from and checked against, and ``sz["positions"]``, the
most a request may take, prompt and output together.
"""
from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import sys
import threading
import time
from unittest import mock

from chipbench import spec


class Req:
    """One request as the client saw it."""

    def __init__(self, rid, tokens, max_new, due):
        self.id, self.tokens, self.max_new, self.due = rid, tokens, max_new, due
        self.sent = None
        self.token_times = []       # perf_counter of each ``token`` event
        self.streamed = []          # the tokens, in order
        self.end = None             # terminal event name
        self.end_time = None
        self.measured = False       # counted in the window's latency metrics

    @property
    def ttft(self):
        return self.token_times[0] - self.due if self.token_times else None


class Client(io.TextIOBase):
    """The client side of the pipe: what a traffic generator drives, and the
    stand-in for the server's stdout that stamps every event as it arrives."""

    def __init__(self, wfd, passthrough, positions):
        self._w = os.fdopen(wfd, "w")
        self._passthrough = passthrough
        self._positions = positions
        self._part = ""
        self.cv = threading.Condition()
        self.reqs = {}
        self.server_gone = False
        self.stray_errors = []
        self.t_open = self.t_close = None
        self.on_open = self.on_close = None     # set by the driver
        self.lateness = []                      # send instant minus due

    # -- server -> client (called from the server's thread) ----------------
    def write(self, s):
        now = time.perf_counter()
        with self.cv:
            self._part += s
            *lines, self._part = self._part.split("\n")
            for line in lines:
                try:
                    ev = json.loads(line)
                except ValueError:          # somebody's print(), not an event
                    self._passthrough.write(line + "\n")
                    continue
                self._on_event(ev, now)
            self.cv.notify_all()
        return len(s)

    def _on_event(self, ev, now):
        req = self.reqs.get(ev.get("id"))
        kind = ev.get("event")
        if req is None:
            if kind == "error":
                self.stray_errors.append(ev.get("reason"))
            return
        if kind == "token":
            req.token_times.append(now)
            req.streamed.append(int(ev["token"]))
        elif kind == "error" and str(ev.get("reason", "")).startswith(
                "cancel:"):
            # the client's own hang-up raced the request's end: the server
            # says "already terminal" before it flushes the terminal event
            pass
        elif kind != "start" and req.end is None:
            req.end, req.end_time = kind, now
            req.end_reason = ev.get("reason") or ev.get("finish_reason")

    def server_exited(self):
        with self.cv:
            self.server_gone = True
            self.cv.notify_all()

    # -- client -> server ---------------------------------------------------
    @staticmethod
    def now():
        return time.perf_counter()

    def send(self, req: Req):
        """Write one request. ``req.due`` is when it was due; the generator
        calls this as close to that instant as it can."""
        if len(req.tokens) + req.max_new > self._positions:
            raise ValueError(
                f"request {req.id}: {len(req.tokens)} + {req.max_new} tokens "
                f"are more than the {self._positions} positions of the "
                "configuration: the mix does not fit it")
        req.sent = time.perf_counter()
        if req.due is None:
            req.due = req.sent
        self.lateness.append(req.sent - req.due)
        with self.cv:
            self.reqs[req.id] = req
        self._w.write(json.dumps({"id": req.id, "tokens": req.tokens,
                                  "max_new_tokens": req.max_new}) + "\n")
        self._w.flush()

    def cancel(self, rid):
        self._w.write(json.dumps({"op": "cancel", "id": rid}) + "\n")
        self._w.flush()

    def wait(self, pred, timeout):
        """Until ``pred()`` holds, the server is gone, or ``timeout`` s."""
        with self.cv:
            return self.cv.wait_for(lambda: self.server_gone or pred(),
                                    timeout)

    def wait_until(self, t):
        """Sleep to instant ``t``; False when the server went away first."""
        while not self.server_gone:
            left = t - time.perf_counter()
            if left <= 0:
                return True
            time.sleep(min(left, 0.05))
        return False

    def outstanding(self):
        return [r for r in self.reqs.values() if r.end is None]

    def open_window(self):
        self.t_open = time.perf_counter()
        if self.on_open:
            self.on_open()

    def close_window(self):
        self.t_close = time.perf_counter()
        if self.on_close:
            self.on_close()

    def hang_up(self):
        """Cancel what is still running and close stdin: the server drains."""
        with contextlib.suppress(OSError, ValueError):
            for r in self.outstanding():
                self.cancel(r.id)
            self._w.close()


def _flags(config, ctx):
    flags = list(config["rehearsal"]["program_flags"] if ctx.rehearse
                 else config["program_flags"])
    # the program's own --seed feeds PRNGKey and NumPy generators of 32 bits
    return flags + ["--seed", str(ctx.seed % (2 ** 31 - 1))]


def run(ctx):
    """Set the server up, let the cell's generator drive it through the
    window, shut it down; return the observations (``obs``)."""
    import jax

    import tnn_tpu.cli.serve as serve_cli
    from tnn_tpu.serving.metrics import ServingMetrics

    config, traffic = ctx.config, ctx.traffic
    reference = spec.plugin("reference", config["reference"])
    sz = ctx.sizes = reference.sizes_of(
        config["rehearsal"] if ctx.rehearse else config)
    params = reference.make_params(sz, ctx.seed)
    jax.block_until_ready(params)
    ctx.note(f"weights made from the seed on {jax.devices()[0].platform}: "
             f"{time.perf_counter() - ctx.t_start:.1f} s after start")

    engines = []

    class Capture(serve_cli.InferenceEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    real_create = serve_cli.models.create

    def create(name, **kw):
        model = real_create(name, **kw)
        reference.check_program(model, sz, name)
        model.init = lambda *a, **k: {"params": params, "state": {}}
        return model

    rfd, wfd = os.pipe()
    client = Client(wfd, passthrough=sys.stderr, positions=sz["positions"])
    err = io.StringIO()
    obs = {"kind": "serve", "client": client, "traffic": traffic,
           "sizes": sz, "params": params, "reference": reference}
    gen = spec.plugin("generators", traffic["generator"])
    marks = {}

    def on_open():
        eng = engines[0]
        marks["keys_open"] = set(eng._jit)
        marks["metrics_before"] = eng.metrics
        eng.metrics = marks["metrics"] = ServingMetrics(eng.profiler)
        ctx.window_opened()

    def on_close():
        eng = engines[0]
        eng.metrics = ServingMetrics(eng.profiler)    # the lead-out's
        marks["keys_close"] = set(eng._jit)
        marks["stats"] = eng.stats()
        ctx.window_closed()

    client.on_open, client.on_close = on_open, on_close

    def client_thread():
        try:
            # the engine exists once main() has built it; then warm-up
            while not engines:
                if client.server_gone:
                    return
                time.sleep(0.02)
            _warm_up(client, config, ctx)
            gen.drive(client, traffic, ctx)
        except BaseException as e:      # report in the main thread
            marks["client_error"] = e
        finally:
            client.hang_up()

    t = threading.Thread(target=client_thread, name="chipbench-client",
                         daemon=True)
    argv = _flags(config, ctx)
    ctx.note("$ tnn-serve " + " ".join(argv))
    with os.fdopen(rfd, "r") as rd, \
            mock.patch.object(serve_cli, "InferenceEngine", Capture), \
            mock.patch.object(serve_cli.models, "create", create), \
            mock.patch.object(sys, "stdin", rd), \
            contextlib.redirect_stdout(client), \
            contextlib.redirect_stderr(_Tee(sys.stderr, err)):
        t.start()
        try:
            rc = serve_cli.main(argv)
        finally:
            client.server_exited()
            t.join(30.0)
    if "client_error" in marks:
        raise marks["client_error"]
    if t.is_alive():
        raise RuntimeError("the client thread did not end")

    eng = engines[0]
    obs.update(
        rc=rc, marks=marks, stderr=err.getvalue(),
        summary=marks["metrics"].summary() if "metrics" in marks else {},
        engine=dict(
            decode_path=marks.get("stats", {}).get("decode_path"),
            paged_fallback_reason=eng.paged_fallback_reason,
            pool_platforms=sorted({d.platform for d in _pages(
                eng.pool.pages_k).sharding.device_set}),
            max_batch_size=eng.scheduler.max_batch_size,
            num_blocks=eng.pool.num_blocks, block_size=eng.pool.block_size,
            program_keys=sorted(map(str, marks.get("keys_close", ())))))
    engines.clear()
    del eng
    gc.collect()            # the pool goes; the reference gets the room
    return obs


def _pages(pages):
    return pages.data if hasattr(pages, "data") else pages


class _Tee(io.TextIOBase):
    def __init__(self, stream, buf):
        self.stream, self.buf = stream, buf

    def write(self, s):
        self.buf.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def _warm_up(client, config, ctx):
    """Send, one after another, a prompt for every chunk-width bucket the
    engine compiles a mixed step for (a prompt of 1, 2, 4 ... tokens is one
    chunk of that width), each followed by a decode step: every program the
    cell's traffic can reach is built or loaded before the window. Lengths
    are the configuration's (``warmup_prompt_lens``)."""
    import numpy as np

    part = config["rehearsal"] if ctx.rehearse else config
    rng = np.random.default_rng([ctx.seed % (2 ** 63), 7])
    vocab = ctx.sizes["vocab_size"]
    t0 = time.perf_counter()
    prompts = [[int(x) for x in rng.integers(0, vocab, n)]
               for n in part["warmup_prompt_lens"]]
    for i, p in enumerate(prompts):
        req = Req(f"warm{i}", p, part["warmup_new_tokens"], None)
        client.send(req)
        if not client.wait(lambda: req.end is not None, 1200.0) \
                or req.end != "done":
            raise RuntimeError(f"warm-up request {i} ({len(p)} tokens) "
                               f"ended {req.end!r}")
    ctx.note(f"warm-up: {len(prompts)} requests, "
             f"{time.perf_counter() - t0:.1f} s")
