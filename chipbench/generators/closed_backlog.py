"""Generator ``closed_backlog``: callers that keep the queue full. A fixed
number of requests is always outstanding; a finished one is replaced at once.
The window opens when the first wave (as many requests as the batch has rows)
has streamed its first token, so every row decodes from the first instant,
and closes ``--seconds`` later by the clock. Parameters, all data in the
traffic file:

  outstanding   requests always in flight
  wave          rows of the batch: the window opens when this many have a
                first token
  requests      [[prompt length, output length], ...] in the order they are
                sent; a run that needs more starts the list again

``--seed`` gives every request its token ids and nothing else: which lengths
there are, how they are paired and in which order they arrive is the file's.
A closed loop's work follows that order (which request ends when, so how
many steps carry a replacement's prefill and how long the rows' contexts are):
with the order drawn from the seed, six seeds read ``out_tok_s`` 51.7 to 54.0
over 2,212 to 2,816 prefill tokens (PERF.md, PR 25). The ids come from the
configuration's vocabulary (``sizes["vocab_size"]``), so one mix serves
configurations of any vocabulary.
"""
from __future__ import annotations

import itertools

import numpy as np

from chipbench.drivers.serve_stdin import Req


def requests(traffic, seed, vocab, scale=1.0):
    """(prompt, max_new) without end, in the order they will be sent."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 2])
    for p, o in itertools.cycle(traffic["requests"]):
        p, o = (max(1, round(n * scale)) for n in (p, o))
        yield [int(x) for x in rng.integers(0, vocab, p)], o


def drive(client, traffic, ctx):
    todo = enumerate(requests(traffic, ctx.seed, ctx.sizes["vocab_size"],
                              ctx.scale))
    sent = []

    def top_up():
        # a request that ends in an error is not replaced: the run is not
        # correct, and a server that refuses everything is not fed for ever
        while len(client.outstanding()) < traffic["outstanding"] and all(
                r.end in (None, "done") for r in sent):
            i, (prompt, max_new) = next(todo)
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
