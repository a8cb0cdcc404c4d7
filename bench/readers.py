"""Arithmetic shared by the metric readers in ``bench/metrics``.

Each reader takes the run's context (`serve.Ctx`) and returns a number,
or None where the run gave it nothing to read.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from devtrace import op_seconds

HOST_PHASES = ("serve.enqueue", "serve.probe", "serve.dispatch")


def _served(ctx):
    return ctx.served_ns >= 0


def percentile_ms(ctx, q: float, since: str = "due") -> Optional[float]:
    """The ``q``-th percentile of served minus due (or enqueue) time,
    over every served request of the window."""
    ok = _served(ctx)
    if not ok.any():
        return None
    base = ctx.due_ns if since == "due" else ctx.enq_ns
    return float(np.percentile((ctx.served_ns[ok] - base[ok]) / 1e6, q))


def admit_wait_ms(ctx, q: float) -> Optional[float]:
    """The ``q``-th percentile of enqueue minus due time."""
    if ctx.due_ns.size == 0:
        return None
    return float(np.percentile((ctx.enq_ns - ctx.due_ns) / 1e6, q))


def lookups_per_s(ctx) -> Optional[float]:
    n = int(np.count_nonzero(_served(ctx)))
    w0, w1 = ctx.window_ns
    return n * ctx.keys_per_request / ((w1 - w0) / 1e9) if n else None


def mean_span_ms(ctx, name: str) -> Optional[float]:
    d = [s[2] - s[1] for s in ctx.window_spans(name)]
    return float(np.mean(d)) / 1e6 if d else None


def host_ms_per_batch(ctx, phases: Sequence[str] = HOST_PHASES
                      ) -> Optional[float]:
    batches = len(ctx.window_spans("serve.dispatch"))
    if not batches:
        return None
    total = sum(s[2] - s[1] for p in phases for s in ctx.window_spans(p))
    return total / 1e6 / batches


def mean_gauge_pct(ctx, name: str) -> Optional[float]:
    v = ctx.window_log(name)
    return 100.0 * float(np.mean(v)) if v else None


def idle_share_pct(ctx) -> Optional[float]:
    if not ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def row_kernels_roofline_pct(ctx, kernels: Sequence[str]
                             ) -> Optional[float]:
    """Share of the HBM roofline the row kernels reach in the window.

    Bytes are those the lookup needs: each token's row read and written
    by the combine, and each residual miss row read and written by the
    gather (the runtime's ``serve.prefetch_stale`` count; batches of a
    tenure with no staging buffer publish none, so their gathers count
    no bytes).  Never the whole tiles the kernels move."""
    t = op_seconds(ctx.trace, kernels)
    if t <= 0:
        return None
    batches = len(ctx.window_spans("serve.dispatch"))
    rows = batches * ctx.tokens_per_batch \
        + sum(ctx.window_log("serve.prefetch_stale"))
    need_s = 2 * rows * ctx.row_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / t


def device_ms_per_batch(ctx) -> Optional[float]:
    """Seconds the device was busy in the traced window, per batch the
    runtime dispatched in it (ms)."""
    batches = len(ctx.window_spans("serve.dispatch"))
    if not ctx.trace or not batches:
        return None
    return 1e3 * ctx.trace["busy_s"] / batches


def replans_per_batch(ctx) -> Optional[float]:
    """Replans (the runtime's ``serve.replans`` count) per batch it
    dispatched, in the traced window."""
    batches = len(ctx.window_spans("serve.dispatch"))
    if not batches:
        return None
    return sum(ctx.window_log("serve.replans")) / batches
