"""``ttft_engine_ms`` in an open-loop cell: the same reading, under a name of
its own because there it moves ``latency_p50_ms``."""
from layer_metrics.ttft_engine_ms import read  # noqa: F401
