"""Decode tokens committed per `engine.step()` over max_batch x burst: the
share of the burst program's slots that produced a token."""


def read(trace, host, cell):
    vals = [v[1] for v in host.samples.get("occupancy", [])]
    return 100.0 * sum(vals) / len(vals) if vals else None
