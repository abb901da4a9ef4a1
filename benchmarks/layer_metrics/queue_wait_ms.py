"""Scheduler and admission: mean queue wait over the window, from the
``quorum_tpu_queue_wait_seconds`` histogram's sum and count at the window's
two scrapes."""


def read(art):
    n = (art["m1"].get("quorum_tpu_queue_wait_seconds_count", 0.0)
         - art["m0"].get("quorum_tpu_queue_wait_seconds_count", 0.0))
    s = (art["m1"].get("quorum_tpu_queue_wait_seconds_sum", 0.0)
         - art["m0"].get("quorum_tpu_queue_wait_seconds_sum", 0.0))
    return s / n * 1000.0 if n > 0 else None
