"""Share of the KV page pool allocated, sampled before every step."""


def read(trace, host, cell):
    vals = [v[1] for v in host.samples.get("pages_used", [])]
    return 100.0 * sum(vals) / len(vals) if vals else None
