"""Arithmetic the per-layer metric readers share.  A reader returns None
where its cell gave it nothing to read; the harness then leaves the metric
out of the line."""
from __future__ import annotations

import re

from chipbench import work


def mean_ms(rec, span: str):
    d = rec.spans.durations(span)
    return 1e3 * sum(d) / len(d) if d else None


def kernel_seconds(rec, pattern: str, span: str | None = None) -> float:
    """Device seconds of the programs whose module name matches; with
    ``span``, only of those started while the host was in that span."""
    rx = re.compile(pattern)
    mods = (rec.trace["modules"] if span is None
            else rec.trace.get("span_modules", {}).get(span, {}))
    return sum(t for name, t in mods.items() if rx.match(name))


def roofline_pct(rec, pattern: str, work_key: str, span: str | None = None):
    """Least time at the chip's peaks for the required (flops, bytes) of
    ``work_key``, over the device time of the matching programs (started in
    ``span``, where given), in %."""
    secs = kernel_seconds(rec, pattern, span)
    flops, nbytes = rec.work.get(work_key, (0, 0))
    if secs <= 0 or flops + nbytes <= 0:
        return None
    return 100.0 * work.least_seconds(flops, nbytes, rec.peaks) / secs


def flops_pct(rec, span: str, work_key: str):
    """Required FLOPs of ``work_key`` over (the span's total time x the
    chip's bf16 peak), in %."""
    secs = sum(rec.spans.durations(span))
    flops = rec.work.get(work_key, 0)
    if secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (secs * rec.peaks["bf16_flops_per_s"])


def idle_pct(rec):
    t = rec.trace
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
