"""Reader ``client_gaps``: a percentile of the gaps between successive
``token`` events of one request, as the client saw them, pooled over the
requests of the window; only gaps that END inside the window."""
from chipbench import stats


def read(obs, percentile):
    client = obs.get("client")
    if client is None:
        return None
    gaps = [1e3 * (b - a) for r in client.reqs.values() if r.measured
            for a, b in zip(r.token_times, r.token_times[1:])
            if client.t_open <= b < client.t_close]
    return stats.percentile(gaps, percentile)
