"""Model step: device time of the admit and prefill-segment programs in the
traced interval per thousand tokens they computed. Both come from the same
events of the profile: an execution's tokens are the rows of the feed-forward
products inside it (trace_reduce.rows_of: members x prompt tokens as padded to
the program's bucket), never the client's clocks. Executions in which the
trace names no such product are left out of both sums; if they hold over a
twentieth of the prefill time the reader reads nothing."""


def known_executions(art):
    """[[rows, executions, seconds], ...] of the prefill executions whose
    rows the trace gives, or None where there is too little to read."""
    t = art["trace"]
    runs = (t or {}).get("prefill_executions") or []
    known = [e for e in runs if e[0] > 0]
    total = sum(e[2] for e in runs)
    if not known or total <= 0 or sum(e[2] for e in known) < 0.95 * total:
        return None
    return known


def read(art):
    known = known_executions(art)
    if not known:
        return None
    tokens = sum(rows * count for rows, count, _ in known)
    return sum(sec for *_, sec in known) * 1000.0 / (tokens / 1000.0)
