"""Generator ``open_loop``: independent users. Requests are due on a schedule
drawn from ``--seed`` before the run and are sent then, whether or not
earlier ones have finished. Every parameter is data in the traffic file:

  rate_per_s          Poisson arrivals per second (fixed: four fifths of the
                      knee)
  lead_in_share       arrivals start this share of a window before it opens,
                      so that batch and prefix cache are in a steady state
  prompt_len, output_len   {"median", "sigma", "min", "max"}: lognormal, clipped
  max_total           prompt + output at most this
  prefixes            [{"len": L, "count": K}, ...] shared system prefixes
  shared_share        share of requests that start with one of them (a
                      prompt too short to carry the one drawn, and a tail of
                      ``min_own_tail`` of its own, shares nothing)
  grace_s             after the window closes the run waits this long, or
                      until every measured request has ended; then it hangs
                      up, and a measured request not ``done`` counts as failed
"""
from __future__ import annotations

import numpy as np

from chipbench.drivers.serve_stdin import Req


def plan(traffic, seed, seconds, vocab, scale=1.0):
    """The run's requests, [(due offset from window open, prompt, max_new)],
    their ids drawn from the configuration's vocabulary of ``vocab`` tokens.
    ``scale`` shrinks every length (the rehearsal's tiny model)."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 1])

    def sc(x):
        return max(1, int(round(x * scale)))

    def lognormal(d):
        x = d["median"] * np.exp(d["sigma"] * rng.standard_normal())
        return sc(min(max(x, d["min"]), d["max"]))

    prefixes = [[int(t) for t in rng.integers(0, vocab, sc(kind["len"]))]
                for kind in traffic["prefixes"] for _ in range(kind["count"])]
    reqs = []
    t = -seconds * traffic["lead_in_share"]
    while True:
        t += rng.exponential(1.0 / traffic["rate_per_s"])
        if t >= seconds:
            return reqs
        n = lognormal(traffic["prompt_len"])
        out = max(1, min(lognormal(traffic["output_len"]),
                         sc(traffic["max_total"]) - n))
        pre = []
        if rng.random() < traffic["shared_share"]:
            pre = prefixes[rng.integers(len(prefixes))]
            if len(pre) + sc(traffic["min_own_tail"]) > n:
                pre = []
        own = [int(x) for x in rng.integers(0, vocab, n - len(pre))]
        reqs.append((t, pre + own, out))


def drive(client, traffic, ctx):
    reqs = plan(traffic, ctx.seed, ctx.seconds, ctx.sizes["vocab_size"],
                ctx.scale)
    t0 = client.now() + ctx.seconds * traffic["lead_in_share"]
    measured = []
    for i, (off, prompt, max_new) in enumerate(reqs):
        if off >= 0 and client.t_open is None:
            client.wait_until(t0)
            client.open_window()
        if not client.wait_until(t0 + off):
            break
        req = Req(f"r{i}", prompt, max_new, t0 + off)
        req.measured = off >= 0
        if req.measured:
            measured.append(req)
        client.send(req)
    if client.t_open is None:
        client.wait_until(t0)
        client.open_window()
    client.wait_until(t0 + ctx.seconds)
    client.close_window()
    client.wait(lambda: all(r.end is not None for r in measured),
                traffic["grace_s"])
