"""The decode attention's share of its roofline, window and full layers
together: the least time to read the LIVE pages of both kinds at the HBM
peak (the burst's own counts on `serving.emit`: `attn_window_pages_live`
for the window layers' rings, `attn_pages_read` for the full layers'
tables; the bytes of a page by the family's table) over the device time
under `attn/window` + `attn/full` in the burst program. HBM-bound: 8
query heads a kv head make 16 operations a byte of K or V."""
from benchmark import (families, flops, program_subscopes, program_trace,
                       trace_reduce)

MODULE = r"pure_burst"
PATHS = ("attn/window", "attn/full")


def page_bytes(cfg, page_size):
    """K and V of one page of one layer: by the family's table, or for a
    configuration without one (GPT: every head its own K and V, as
    `flops.decode_bytes` counts a token) 2 x hidden numbers a token."""
    need = families.needs(cfg)
    if hasattr(need, "page_bytes"):
        return need.page_bytes(cfg, page_size)
    return page_size * 2 * cfg["hidden_size"] * flops.BF16


def read(trace, host, cell):
    engine = cell.config["engine"]
    per_step = [program_subscopes.path_ms(trace, MODULE, p,
                                          engine["decode_burst"])
                for p in PATHS]
    if None in per_step:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    seconds = sum(per_step) * runs * engine["decode_burst"] / 1e3
    emits = program_trace.marks(program_trace.current(trace), "serving.emit")
    pages = sum(a.get("attn_window_pages_live", 0)
                + a.get("attn_pages_read", 0) for a in emits)
    if not pages or seconds <= 0:
        return None
    return 100.0 * pages * page_bytes(cell.config, engine["page_size"]) \
        / cell.peaks["hbm_bytes_per_s"] / seconds
