"""generator_lag_p95_ms.serve: 95th percentile of how late the load
generator submitted each request after it was due (host clock)."""
from bench.drivers import quantile


def read(ctx, peaks):
    lags = ctx.counters.get("generator_lag_s") or []
    if not lags:
        return None
    return 1e3 * quantile(lags, 0.95)
