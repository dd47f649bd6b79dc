"""``ttft_p90_ms``: client side, from the instant a request was DUE to its
first ``token`` event; 90th percentile over ALL requests due in the window.
A measured request with no first token (failed, refused, too late) counts at
the time it had waited when the run gave it up: it cannot pull the tail in."""
from chipbench import stats


def samples(obs):
    client = obs["client"]
    t_give_up = max([client.t_close] + [
        r.end_time for r in client.reqs.values() if r.end_time])
    return [1e3 * (r.ttft if r.ttft is not None else t_give_up - r.due)
            for r in client.reqs.values() if r.measured]


def value(obs):
    return stats.percentile(samples(obs), 90)
