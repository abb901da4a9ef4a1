"""Scheduler and admission: prefill segment programs dispatched per scheduler
turn that dispatched any, ahead of the turn's decode chunk: the rise of
``prefill_segments_total`` over the rise of ``prefill_segment_turns_total``
between the window's scrapes. 1 is a segment a turn; a long prompt's first
token waits for about (its segments / this) decode chunks."""

SEGMENTS = "quorum_tpu_engine_prefill_segments_total"
TURNS = "quorum_tpu_engine_prefill_segment_turns_total"


def delta(art, key):
    """A counter's rise between the scrapes; None where a scrape lacks it."""
    if key not in art["m0"] or key not in art["m1"]:
        return None
    return art["m1"][key] - art["m0"][key]


def read(art):
    segments, turns = delta(art, SEGMENTS), delta(art, TURNS)
    if segments is None or not turns or turns <= 0:
        return None
    return segments / turns
