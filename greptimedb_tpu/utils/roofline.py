"""Roofline accountant: fold a query's resource ledger into bandwidth.

The per-query :mod:`ledger` already counts every byte a statement moves
(H2D/D2H transfers from device_telemetry, decoded scan bytes from the
storage plane) and every millisecond its device spans ran.  This module
folds those raw counts into three numbers:

- ``achieved_gbps``  — bytes moved / device time, in GB/s.  The bytes
  are ``h2d_bytes + d2h_bytes + bytes_decoded`` (link traffic plus the
  decode read stream); the denominator prefers device span time
  (``device_ms``), falling back to aggregate time and finally to the
  caller-supplied wall duration.
- ``arithmetic_intensity`` — estimated FLOPs per byte.  The workloads
  here are streaming reductions (~one multiply-accumulate per scanned
  row), so intensity lands well under 1 FLOP/B: bandwidth-bound, which
  is exactly why achieved GB/s is the number that matters.
- ``roofline_fraction`` — achieved_gbps / the device's published peak
  memory bandwidth, looked up by ``device_kind`` (overridable for golden
  tests via ``GTPU_ROOFLINE_PEAK_GBPS``). A device that is not in the
  table gets NO fold at all — never a default peak.

This is a transfer-rate gauge over host-clock span time, not a kernel
roofline: kernel time comes only from a profiler trace.

Everything is a pure fold over a ledger snapshot dict — no sampling, no
probes at account() time — so the stamped numbers agree with the ledger
byte counts exactly, and golden tests can hand-compute fixtures.
"""

from __future__ import annotations

import os
from typing import Optional

#: peak memory bandwidth by jax ``device_kind``, GB/s, with its source.
#: An unknown device is absent on purpose: no peak, no roofline line.
_PEAK_GBPS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e, 819 GB/s/chip
    "TPU v5 lite": 819.0,
}

#: estimated FLOPs per scanned row — one multiply-accumulate, the
#: honest floor for the streaming SUM/AVG reductions this engine runs
_EST_FLOPS_PER_ROW = 2.0

#: ledger keys folded into the byte numerator, in stamp order
BYTE_KEYS = ("h2d_bytes", "d2h_bytes", "bytes_decoded")


def peak_gbps(device_kind: Optional[str] = None) -> Optional[float]:
    """Published peak memory bandwidth in GB/s of `device_kind` (default:
    the process's first device), or None for a device the table does
    not know. ``GTPU_ROOFLINE_PEAK_GBPS`` overrides — used by golden
    tests for determinism."""
    env = os.environ.get("GTPU_ROOFLINE_PEAK_GBPS", "").strip()
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            pass
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return _PEAK_GBPS.get(device_kind)


def account(led: dict, duration_ms: Optional[float] = None,
            peak: Optional[float] = None) -> Optional[dict]:
    """Fold a ledger snapshot/diff dict into roofline terms.

    Returns None when the ledger moved no bytes or recorded no usable
    time window — host-only statements (DDL, information_schema) have
    no meaningful bandwidth and must not stamp a misleading zero — and
    when the device's peak is unknown (see peak_gbps).
    """
    bytes_total = 0.0
    for k in BYTE_KEYS:
        try:
            bytes_total += float(led.get(k, 0) or 0)
        except (TypeError, ValueError):
            continue
    ms = led.get("device_ms") or led.get("agg_ms") or duration_ms
    try:
        ms = float(ms) if ms is not None else 0.0
    except (TypeError, ValueError):
        ms = 0.0
    if bytes_total <= 0 or ms <= 0:
        return None
    gbps = bytes_total / (ms / 1e3) / 1e9
    if peak is None:
        peak = peak_gbps()
    if peak is None:
        return None
    try:
        rows = float(led.get("rows_scanned", 0) or 0)
    except (TypeError, ValueError):
        rows = 0.0
    return {
        "achieved_gbps": gbps,
        "roofline_fraction": gbps / peak,
        "arithmetic_intensity": (_EST_FLOPS_PER_ROW * rows) / bytes_total,
        "bytes_total": int(bytes_total),
        "window_ms": ms,
        "peak_gbps": peak,
    }


def stamp(attrs: dict, led: dict,
          duration_ms: Optional[float] = None) -> Optional[dict]:
    """account() + write the two headline numbers into a span's attrs.

    The full fold is returned so callers (slow-query records, ANALYZE)
    can surface the supporting terms too.
    """
    rf = account(led, duration_ms)
    if rf is not None:
        attrs["achieved_gbps"] = round(rf["achieved_gbps"], 6)
        attrs["roofline_fraction"] = round(rf["roofline_fraction"], 9)
    return rf


def format_line(rf: dict) -> str:
    """One ANALYZE-style text line for a fold, stable for tooling."""
    return (f"achieved_gbps={rf['achieved_gbps']:.6g} "
            f"roofline_fraction={rf['roofline_fraction']:.6g} "
            f"arithmetic_intensity={rf['arithmetic_intensity']:.6g} "
            f"bytes={rf['bytes_total']} window_ms={rf['window_ms']:.6g} "
            f"peak_gbps={rf['peak_gbps']:g}")
