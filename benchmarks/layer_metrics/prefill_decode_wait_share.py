"""Scheduler and admission: of the chunked admissions' ``prefill`` spans
(slot claim to register), the share the loop spent on other rows' decode
chunks between the segments (its time in the chunk step less the device's
time on segment programs): ``prefill_decode_wait_seconds_total`` over
``prefill_span_seconds_total`` between the window's scrapes, in percent."""

WAIT = "quorum_tpu_engine_prefill_decode_wait_seconds_total"
SPAN = "quorum_tpu_engine_prefill_span_seconds_total"


def delta(art, key):
    """A counter's rise between the scrapes; None where a scrape lacks it."""
    if key not in art["m0"] or key not in art["m1"]:
        return None
    return art["m1"][key] - art["m0"][key]


def read(art):
    wait, span = delta(art, WAIT), delta(art, SPAN)
    if wait is None or not span or span <= 0:
        return None
    return 100.0 * wait / span
