"""Of the pages a layer holding every position would read in a decode
step, the share that holds a position some row can still see through its
window: `attn_window_pages_live` over `attn_window_pages_context`, counted
by the burst program from the rows' lengths and handed to `serving.emit`.
What a window layer has to read; 100 for contexts inside the window."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_pct(
        trace, "attn_window_pages_live", "attn_window_pages_context")
