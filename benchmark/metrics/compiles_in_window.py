"""Executables the persistent compile cache gained during the window
(`framework/compile_cache.entry_count()` after - before): from a cell's
second run in a checkout this must read 0."""


def read(trace, host, cell):
    return host.counts.get("compiles_in_window")
