"""How late the load generator handed requests to the engine: p95 of
(add_request time - due time). A starved generator must not read as a fast
server."""
from benchmark.hostlog import percentile


def read(trace, host, cell):
    lags = [1e3 * v[1] for v in host.samples.get("gen_lag_s", [])]
    return percentile(lags, 95)
