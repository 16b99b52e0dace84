"""Share of the traced part in which no operation ran on the busiest
device."""


def read(trace, host, cell):
    if trace is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    busy = max(d["busy_s"] for d in trace["devices"])
    return 100.0 * (1.0 - busy / trace["window_s"])
