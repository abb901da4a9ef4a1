"""From the client's records to the end-to-end metrics: order statistics
over the window's requests, and the rate of tokens delivered inside the
window. Kept with the benchmark so that no later PR can change it."""

from __future__ import annotations

import math


def percentile(values: list, q: float):
    """Nearest-rank order statistic: the smallest value with at least
    ``q`` of the sample at or below it. None for an empty sample."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def kth_largest(values: list, k: int):
    s = sorted(values, reverse=True)
    return s[k - 1] if len(s) >= k else None


def window_records(records: list) -> list:
    return [r for r in records if r["phase"] == "window"]


def layer_records(art: dict) -> list:
    """The window's requests a traced run's per-layer readers look at: those
    due before ``read_until_s``, where the profile starts."""
    return [r for r in window_records(art["records"])
            if r["due"] < art["read_until_s"]]


def member_streams(rec: dict) -> list:
    """The token-bearing streams of one request: each member of a quorum,
    or the single backend's. The combined final chunk repeats the members'
    text and is no stream of tokens."""
    return [s for k, s in rec["streams"].items() if k != "final"]


def stream_tokens(rec: dict, s: dict) -> int:
    """Completion tokens of one stream: what the server's usage chunk says
    where there is one (single backend), else what was asked (``max_tokens``
    fixes the length; the quorum merge forwards text only)."""
    return int(s["tokens"]) if s.get("tokens") is not None else int(
        rec["max_tokens"])


def failed(rec: dict) -> bool:
    """A request that was refused, broke, or ended with nothing to show. A
    stream whose first sampled token was the end-of-sequence id (about one
    in 32000 under random weights) finishes with ``stop`` and no content:
    the system answered it rightly, so it has not failed; it gives no
    latency sample."""
    return (rec["status"] != 200 or bool(rec["error"]) or rec["end"] is None
            or not any(s["deltas"] or s.get("finish") == "stop"
                       for s in member_streams(rec)))


def ttft_ms(records: list) -> list:
    """Per request: from when it was due (open loop) or sent (closed loop,
    where due is the send decision) to its first content token."""
    return [(r["first"] - r["due"]) * 1000.0 for r in records
            if not failed(r) and r["first"] is not None]


def tpot_ms(records: list) -> list:
    """Per member stream: (last token time - first token time) /
    (completion tokens - 1)."""
    out = []
    for r in records:
        if failed(r):
            continue
        for s in member_streams(r):
            n = stream_tokens(r, s)
            if s["first"] is not None and n > 1:
                out.append((s["last"] - s["first"]) * 1000.0 / (n - 1))
    return out


def latency_ms(records: list) -> list:
    return [(r["end"] - r["due"]) * 1000.0 for r in records if not failed(r)]


def tokens_in_window(records: list, window_s: float) -> float:
    """Completion tokens delivered inside [0, window_s) by every stream of
    every request, counted or not: a stream's tokens lie evenly between its
    first and its last content delta (decode steps are regular), and the
    part of them inside the window counts. All the work, all the time."""
    total = 0.0
    for r in records:
        for s in member_streams(r):
            if s["first"] is None:
                continue
            n = stream_tokens(r, s) if r["end"] is not None else None
            if n is None:
                continue  # cut off by the generator: its length is unknown
            first, last = s["first"], s["last"]
            if n <= 1 or last <= first:
                total += n if 0.0 <= first < window_s else 0
                continue
            step = (last - first) / (n - 1)  # token i arrives at first + i*step
            total += sum(1 for i in range(n)
                         if 0.0 <= first + i * step < window_s)
    return total


def end_to_end(records: list, window_s: float, setup_s: float) -> dict:
    """Every end-to-end metric the records can give, by name."""
    win = window_records(records)
    ttft = ttft_ms(win)
    return {
        "ttft_p50_ms": percentile(ttft, 0.5),
        "ttft_p90_ms": percentile(ttft, 0.9),
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "tpot_p50_ms": percentile(tpot_ms(win), 0.5),
        "latency_p50_ms": percentile(latency_ms(win), 0.5),
        "tokens_per_s": tokens_in_window(records, window_s) / window_s,
        "setup_s": setup_s,
    }
