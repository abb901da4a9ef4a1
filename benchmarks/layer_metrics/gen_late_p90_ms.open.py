"""``gen_late_p90_ms`` in an open-loop cell: the same reading, under a name of its own
because there it moves ``latency_p50_ms`` (no time-to-first-token statistic
of an open loop's few dozen requests repeats well enough to bound)."""
from layer_metrics.gen_late_p90_ms import read  # noqa: F401
