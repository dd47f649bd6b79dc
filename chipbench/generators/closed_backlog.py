"""Generator ``closed_backlog``: callers that keep the queue full. A fixed
number of requests is always outstanding; a finished one is replaced at once.
The window opens when the first wave (as many requests as the batch has rows)
has streamed its first token, so every row decodes from the first instant,
and closes ``--seconds`` later by the clock. Parameters, all data in the
traffic file:

  outstanding         requests always in flight
  wave                rows of the batch: the window opens when this many
                      have a first token
  prompt_len, output_len   {"min", "max"}: uniform
  pool                requests prepared per run, a multiple of ``wave``
  vocab_size

Every ``wave`` requests in a row hold the same lengths, evenly spaced over
the range, in an order drawn from ``--seed``, so the rows that decode through
a window hold the same contexts under every seed. A decode step's time grows
with the pages its rows fill (634 ms at 27% of the pool, 653 ms at 41%;
PERF.md, PR 23): lengths drawn freely over the pool would let the seed change
the work.
"""
from __future__ import annotations

import numpy as np

from chipbench.drivers.serve_stdin import Req


def plan(traffic, seed, scale=1.0):
    """[(prompt, max_new)] in the order they will be sent."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 2])
    wave, waves = traffic["wave"], traffic["pool"] // traffic["wave"]

    def lengths(span):
        lo, hi = (max(1, round(span[k] * scale)) for k in ("min", "max"))
        even = [round(lo + (hi - lo) * (i + 0.5) / wave) for i in range(wave)]
        return [even[i] for _ in range(waves) for i in rng.permutation(wave)]

    prompts = lengths(traffic["prompt_len"])
    outs = lengths(traffic["output_len"])
    return [([int(x) for x in rng.integers(0, traffic["vocab_size"], p)], o)
            for p, o in zip(prompts, outs)]


def drive(client, traffic, ctx):
    todo = list(enumerate(plan(traffic, ctx.seed, ctx.scale)))
    sent = []

    def top_up():
        while todo and len(client.outstanding()) < traffic["outstanding"]:
            i, (prompt, max_new) = todo.pop(0)
            req = Req(f"r{i}", prompt, max_new, None)
            req.measured = True
            sent.append(req)
            client.send(req)

    top_up()
    first = sent[:traffic["wave"]]
    if not client.wait(lambda: all(r.token_times or r.end for r in first),
                       1200.0):
        return
    client.open_window()
    t_end = client.t_open + ctx.seconds
    while client.now() < t_end and not client.server_gone:
        n_out = len(client.outstanding())
        client.wait(lambda: len(client.outstanding()) < n_out,
                    min(0.05, max(0.0, t_end - client.now())))
        top_up()
    client.close_window()
    if not todo:
        ctx.note("closed_backlog: the prepared pool of requests ran out "
                 "inside the window; raise 'pool' in the traffic file")
