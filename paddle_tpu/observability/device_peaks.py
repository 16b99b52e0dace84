"""Device peak table: the ONE source of truth for per-chip bf16 peak
FLOPs and HBM bandwidth (README.md "Step-time ledger").

Before this module the peak numbers lived in three places — the
PerfMeter MFU gauge (`profiler/perf_meter.py`), bench.py's MFU line,
and the sweep tooling — and a corrected spec (v5e's headline 394 TOPS
is INT8, bf16 is half) had to be fixed three times. Now every MFU and
roofline computation (PerfMeter, bench.py, tools/mfu_sweep.py, the
stepledger channel) reads this table; tests/test_stepledger.py pins
that they agree.

Import-light ON PURPOSE: no jax at module import, so standalone tools
(tools/mfu_sweep.py loads this file via importlib without touching the
package __init__) can read the table without paying the framework
import. `detect_*` helpers import jax lazily and degrade to the given
default (None) on CPU/GPU dev boxes — MFU/roofline are then omitted
rather than computed against a meaningless peak.
"""
from __future__ import annotations

from typing import Optional

# bf16 peak FLOPs per chip by generation (public TPU specs; note v5e's
# headline 394 TOPS is INT8 — bf16 is half that)
PEAK_FLOPS_BF16 = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# HBM bandwidth per chip, bytes/s (public TPU specs) — the denominator
# of the roofline ridge point (peak_flops / peak_bw = the arithmetic
# intensity above which a kernel is compute-bound, below it HBM-bound)
PEAK_HBM_BYTES_PER_S = {
    "v4": 1228e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
}

def normalize_kind(device_kind: str) -> Optional[str]:
    """Map a jax `device_kind` string onto a table key (None when
    unrecognized). A generation is matched only when the string names
    it: v5e reports "TPU v5 lite", and a bare "TPU v5" is NOT taken for
    v5p."""
    kind = (device_kind or "").lower()
    if "v5 lite" in kind or "v5e" in kind:
        return "v5e"
    if "v5p" in kind:
        return "v5p"
    if "v4" in kind:
        return "v4"
    if "v6 lite" in kind or "v6e" in kind:
        return "v6e"
    return None


def require_kind(device_kind: str) -> str:
    """Table key for `device_kind`, or ValueError: measurement entry
    points must not divide by another chip's peak."""
    kind = normalize_kind(device_kind)
    if kind is None:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peak table "
            f"(observability/device_peaks.py: "
            f"{sorted(PEAK_FLOPS_BF16)}); add its published peaks "
            f"before measuring on it")
    return kind


def detect_kind(default: Optional[str] = None) -> Optional[str]:
    """Table key for the process's default device (lazy jax import);
    `default` (None) for CPU/GPU dev boxes."""
    try:
        import jax

        kind = normalize_kind(jax.devices()[0].device_kind)
        if kind is not None:
            return kind
    except Exception:  # noqa: BLE001 — telemetry must never raise
        pass
    return default


def peak_flops(kind: Optional[str] = None, default=None):
    """bf16 peak FLOPs/s for `kind` (auto-detected when None); `default`
    for unrecognized devices."""
    k = kind if kind is not None else detect_kind()
    return PEAK_FLOPS_BF16.get(k, default) if k else default


def peak_hbm_bytes_per_s(kind: Optional[str] = None, default=None):
    """HBM bytes/s for `kind` (auto-detected when None)."""
    k = kind if kind is not None else detect_kind()
    return PEAK_HBM_BYTES_PER_S.get(k, default) if k else default


def detect_peak_flops(default=None):
    """Best-effort bf16 peak from the device kind string (the historical
    profiler.perf_meter entry point — kept as the compatibility name)."""
    return peak_flops(default=default)


def detect_peak_hbm_bytes_per_s(default=None):
    return peak_hbm_bytes_per_s(default=default)
