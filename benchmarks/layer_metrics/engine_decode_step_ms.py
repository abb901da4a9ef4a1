"""Scheduler and admission: a decode step's device time over the whole
window, by the engine's own device ledger: the rise of
``device_decode_seconds_total`` (every decode dispatch's seconds, booked
landing to landing on the scheduler's clock) over the rise of
``device_decode_steps_total`` (steps executed) between the window's scrapes,
in ms. ``decode_step_ms`` reads the same off the last 2 s's profile."""
from layer_metrics.prefill_decode_wait_share import delta

DEVICE = tuple(f"quorum_tpu_engine_device_{kind}_seconds_total"
               for kind in ("decode", "prefill", "other", "starved"))
SECONDS = DEVICE[0]
STEPS = "quorum_tpu_engine_device_decode_steps_total"


def device_seconds(art):
    """The rise of the ledger's four accounts together: the scheduler
    loop's wall clock between the scrapes, less the time it had no work
    (``device_idle_seconds_total``, kept apart: nobody held the device up
    then). None unless a scrape has all four."""
    rises = [delta(art, key) for key in DEVICE]
    return None if None in rises else sum(rises)


def read(art):
    seconds, steps = delta(art, SECONDS), delta(art, STEPS)
    if seconds is None or not steps or steps <= 0:
        return None
    return 1000.0 * seconds / steps
