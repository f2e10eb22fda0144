"""Arithmetic from request records to end-to-end metrics. Pure Python.

A record is what the load generator stamped for one request, all times on
the machine's monotonic clock:

    {"index", "due", "sent", "head", "token_ts": [...], "tokens": [...],
     "end", "finish_reason", "status", "error", "request_id",
     "prompt_tokens", "max_tokens"}

The window is ``[t0, t1)``. A latency is taken over every request that was
due in the window; one that failed, was refused or never finished counts
with the time until observation ended (``observed_until``), which is a
miss of any limit a reader sets. A rate is all the work over all the time.
"""

from __future__ import annotations

import math
from typing import Any, Iterable

OK_REASONS = ("length", "stop")


def percentile(values: Iterable[float], q: float) -> float:
    """The ``int(q*n)``-th order statistic (loadlab/scorer.py's rule):
    no interpolation, so every reported value is one that was observed."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    return ordered[min(int(q * len(ordered)), len(ordered) - 1)]


def succeeded(rec: dict[str, Any]) -> bool:
    """Served: a 200, no error, ended by its own budget or a natural stop,
    with a token — or with none where the model chose EOS first (the EOS
    is chosen and counted but never streamed)."""
    return (
        rec.get("status") == 200
        and rec.get("error") is None
        and rec.get("finish_reason") in OK_REASONS
        and (len(rec.get("token_ts") or ()) >= 1 or rec.get("finish_reason") == "stop")
    )


def first_token_time(rec: dict[str, Any]) -> float | None:
    """When the first token frame came; for an answer that is EOS alone,
    when its terminal frame came."""
    ts = rec.get("token_ts") or ()
    return ts[0] if ts else rec.get("end")


def failed(rec: dict[str, Any], closed_loop: bool = False) -> bool:
    """Not served, and not merely cut off by the end of observation in a
    closed loop (which keeps requests in flight at the close by design).
    In an open loop an answer that never came is a failure."""
    return not succeeded(rec) and not (closed_loop and rec.get("cut") and rec.get("error") is None)


def due_in_window(records: list[dict], t0: float, t1: float) -> list[dict]:
    return [r for r in records if t0 <= r["due"] < t1]


def ttft_ms(rec: dict, observed_until: float) -> float:
    """First token frame minus the DUE time; a request with no first token
    is charged everything up to the end of observation."""
    first = first_token_time(rec) if succeeded(rec) else observed_until
    return (first - rec["due"]) * 1e3


def tpot_ms(rec: dict, observed_until: float) -> float | None:
    """(last token - first token) / (tokens - 1): a stall anywhere in the
    request's decode moves it. None for a served request of one token (it
    has no gap); a failed request is charged its whole observed time."""
    if not succeeded(rec):
        return (observed_until - rec["due"]) * 1e3
    ts = rec["token_ts"]
    if len(ts) < 2:
        return None
    return (ts[-1] - ts[0]) / (len(ts) - 1) * 1e3


def tokens_per_s(records: list[dict], t0: float, t1: float) -> float:
    """(prompt tokens of requests whose first token arrived in the window
    + output tokens received in the window) / window seconds. Requests
    that later fail still count the tokens they were sent: it is the work
    the device did in the window."""
    total = 0
    for r in records:
        ts = r.get("token_ts") or ()
        if ts and t0 <= ts[0] < t1:
            total += int(r["prompt_tokens"])
        total += sum(1 for t in ts if t0 <= t < t1)
    return total / (t1 - t0)


def window_tokens(records: list[dict], t0: float, t1: float) -> dict[str, int]:
    """Counts behind :func:`tokens_per_s`, and the resident-length sum the
    paged-attention byte count needs: a token received as the j-th output
    (j from 1) of a request of P prompt tokens was produced by a decode
    step that read P + j - 1 positions; the first token comes from the
    prefill and reads none through the paged kernel."""
    prompt = output = decode_tokens = resident = 0
    for r in records:
        ts = r.get("token_ts") or ()
        p = int(r["prompt_tokens"])
        if ts and t0 <= ts[0] < t1:
            prompt += p
        for j, t in enumerate(ts, start=1):
            if t0 <= t < t1:
                output += 1
                if j > 1:
                    decode_tokens += 1
                    resident += p + j - 1
    return {"prompt_tokens": prompt, "output_tokens": output,
            "decode_tokens": decode_tokens, "resident_positions": resident}


def summarize(records: list[dict], t0: float, t1: float,
              observed_until: float, closed_loop: bool = False) -> dict[str, Any]:
    """Every end-to-end number a cell may report, with the sample count
    beside each percentile. The caller picks the ones its cell names."""
    due = due_in_window(records, t0, t1)
    out: dict[str, Any] = {
        "attempted": len(due),
        "failed": sum(failed(r, closed_loop) for r in due),
        "cut": sum(bool(r.get("cut")) for r in due),
        "tok_s": tokens_per_s(records, t0, t1),
    }
    if due:
        ttfts = [ttft_ms(r, observed_until) for r in due]
        tpots = [v for v in (tpot_ms(r, observed_until) for r in due) if v is not None]
        out["ttft_mean_ms"] = sum(ttfts) / len(ttfts)
        out["ttft_p50_ms"] = percentile(ttfts, 0.50)
        out["ttft_p90_ms"] = percentile(ttfts, 0.90)
        out["ttft_samples"] = len(ttfts)
        if tpots:
            out["tpot_p90_ms"] = percentile(tpots, 0.90)
            out["tpot_samples"] = len(tpots)
    lateness = [r["sent"] - r["due"] for r in due if r.get("sent") is not None]
    if lateness:
        out["generator_late_p50_ms"] = percentile(lateness, 0.5) * 1e3
        out["generator_late_max_ms"] = max(lateness) * 1e3
    return out


def iqr_share(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the driver's spread."""
    import statistics

    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else math.inf
