"""Server, strategy merge and backend: mean time from the earliest member's
first backend delta to the first content write on the wire: the strategy's
part (``quorum_tpu_first_token_strategy_seconds``: merge queue, thinking
filter, encoding) plus the writer's (``quorum_tpu_first_token_wire_seconds``:
write coalescing), between the window's scrapes. Per request."""
from layer_metrics.ttft_engine_ms import mean_ms


def read(art):
    parts = [mean_ms(art, "quorum_tpu_first_token_strategy_seconds"),
             mean_ms(art, "quorum_tpu_first_token_wire_seconds")]
    return None if None in parts else sum(parts)
