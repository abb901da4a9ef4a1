"""Server, strategy merge and backend: median over the window's requests of
client time-to-first-token (from the send) minus the engine's own spans for
that request from /debug/traces: queue wait + prefill, + the first decode
chunk where the prefill was chunked (its first token comes from that chunk).
What is left is HTTP, tokenising, the fan-out, the merge and the SSE write."""
from e2e import failed, layer_records, percentile


def engine_first_token_ms(trace: dict):
    spans = trace.get("spans") or []
    best = None
    members = {(s.get("meta") or {}).get("member", 0) for s in spans
               if s["name"] == "queue-wait"} or {0}
    for member in members:
        qw = [s for s in spans if s["name"] == "queue-wait"
              and (s.get("meta") or {}).get("member", 0) == member]
        if not qw:
            continue
        # a member's prefill and decode spans follow its queue wait
        after = [s for s in spans if s["start_s"] >= qw[0]["end_s"] - 1e-6]
        pre = [s for s in after if s["name"] == "prefill"]
        if not pre:
            continue
        total = qw[0]["duration_ms"] + pre[0]["duration_ms"]
        if (pre[0].get("meta") or {}).get("chunked"):
            dec = [s for s in after if s["name"] == "decode"
                   and s["start_s"] >= pre[0]["end_s"] - 1e-6]
            if dec:
                total += dec[0]["duration_ms"]
        best = total if best is None else min(best, total)
    return best


def read(art):
    vals = []
    for r in layer_records(art):
        trace = art["spans"].get(r.get("rid"))
        if failed(r) or trace is None or r["first"] is None:
            continue
        eng = engine_first_token_ms(trace)
        if eng is not None:
            vals.append((r["first"] - r["sent"]) * 1000.0 - eng)
    return percentile(vals, 0.5)
