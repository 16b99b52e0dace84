"""Host time inside the train step's call until it returns (the dispatch;
the device works on after it), mean over the steps."""


def read(trace, host, cell):
    spans = [e - s for name, s, e in host.spans if name == "step"]
    return 1e3 * sum(spans) / len(spans) if spans else None
