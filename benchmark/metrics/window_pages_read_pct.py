"""Of the pages a layer holding every position would read in a decode
step, the share the window layers' attention STREAMS:
`attn_window_pages_read` over `attn_window_pages_context`, counted by the
burst program and handed to `serving.emit`. `window_pages_live_pct` where
the kernel reads the live pages and nothing else; 100 for a program that
reads whole contexts."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_pct(
        trace, "attn_window_pages_read", "attn_window_pages_context")
