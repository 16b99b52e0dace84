"""The decode attention's share of its roofline, window and full layers
together: the least time to read the LIVE window pages and the full pages
read (the burst's own counts on `serving.emit`: `attn_window_pages_live`
for the window layers' rings, `attn_pages_read` for the full layers'
tables), each at its kind's `page_bytes` by the family's table (the TRUE
widths of a key and a value, whatever a pool pads a key to; a family whose
layers share one layout says one size for both), at the HBM peak, over
the device time under `attn/window` + `attn/full` in the burst program.
HBM-bound: 8 or 16 query heads a kv head make 16 or 32 operations a byte
of K or V."""
from benchmark import (families, program_subscopes, program_trace,
                       trace_reduce)

MODULE = r"pure_burst"
PATHS = ("attn/window", "attn/full")


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
    window = sum(a.get("attn_window_pages_live", 0) for a in emits)
    full = sum(a.get("attn_pages_read", 0) for a in emits)
    if not window + full or seconds <= 0:
        return None
    size, need = engine["page_size"], families.needs(cell.config)
    least = (window * need.page_bytes(cell.config, size, "window")
             + full * need.page_bytes(cell.config, size, "full")) \
        / cell.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
