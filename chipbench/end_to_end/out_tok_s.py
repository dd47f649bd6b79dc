"""``out_tok_s``: ``token`` events received inside the window, whether or not
their request ended there, over the time they took to arrive.

``tnn-serve`` writes its events out in flushes (one per decode step, and at
most one per 50 ms poll of its front end), so tokens arrive in bursts: 8 at a
time every 0.65 s at PR 23. A count of events between two instants of the
clock then reads one burst more or less by where the clock cut falls: the
51 s window held 78 or 79 bursts and ``out_tok_s`` took exactly two values,
1.27% apart (PERF.md, PR 23). So the rate is taken from the end of the
window's first flush to its last event: every token after the first flush,
over the time from that flush to the last. That leaves out less than two
steps at the window's edges and nothing in between."""

# events stamped closer together than this are one flush of the front end
# (half its poll of 50 ms, cli/serve.py)
FLUSH_S = 0.025


def value(obs):
    client = obs["client"]
    ts = sorted(t for r in client.reqs.values() for t in r.token_times
                if client.t_open <= t < client.t_close)
    i = 0       # the last event of the first flush
    while i + 1 < len(ts) and ts[i + 1] - ts[i] < FLUSH_S:
        i += 1
    if not ts or ts[-1] <= ts[i]:
        return None
    return (len(ts) - 1 - i) / (ts[-1] - ts[i])
