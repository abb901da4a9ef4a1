"""Scheduler and admission: the engine's part of a first token, as the
program stamps it: mean queue wait (submit to slot claim) plus mean
``quorum_tpu_first_token_prefill_seconds`` (slot claim to the engine's first
emitted token), each from its histogram's sum and count at the window's two
scrapes. Per engine submission, so a quorum's three members count three
times. Nothing is read unless both families are in both scrapes."""

FAMILIES = ("quorum_tpu_queue_wait_seconds",
            "quorum_tpu_first_token_prefill_seconds")


def mean_ms(art, family):
    """Mean of a histogram family between the scrapes, in ms; None where a
    scrape lacks the family or nothing was observed between them."""
    keys = (family + "_sum", family + "_count")
    if any(k not in art[m] for m in ("m0", "m1") for k in keys):
        return None
    s, n = (art["m1"][k] - art["m0"][k] for k in keys)
    return s / n * 1000.0 if n > 0 else None


def read(art):
    parts = [mean_ms(art, f) for f in FAMILIES]
    return None if None in parts else sum(parts)
