"""``itl_p95_ms``: client side, the gap between successive ``token`` events
of one request, pooled over every request due in the window; 95th
percentile."""
from chipbench import stats


def samples(obs):
    return [1e3 * g for r in obs["client"].reqs.values() if r.measured
            for g in stats.gaps(r.token_times)]


def value(obs):
    return stats.percentile(samples(obs), 95)
