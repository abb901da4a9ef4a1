"""Scheduler and admission: of the chunked admissions' ``prefill`` spans
(slot claim to register), the share the device spent on prefill programs
that advanced *other* admissions: the rise of ``prefill_peer_seconds_total``
over the rise of ``prefill_span_seconds_total`` between the window's
scrapes, in %. Beside it on ``/metrics``: ``prefill_own_seconds_total`` (the
admission's own programs) and ``prefill_decode_wait_seconds_total``."""
from layer_metrics.prefill_decode_wait_share import delta

PEER = "quorum_tpu_engine_prefill_peer_seconds_total"
SPAN = "quorum_tpu_engine_prefill_span_seconds_total"


def read(art):
    peer, span = delta(art, PEER), delta(art, SPAN)
    if peer is None or not span or span <= 0:
        return None
    return 100.0 * peer / span
