"""Server, strategy merge and backend: mean time from the engine's first
emitted token to the tpu:// backend's first non-empty content delta
(``quorum_tpu_first_token_backend_seconds`` between the window's scrapes):
the consumer thread's wake-up, the detokenizer holding back a token that
ends inside a character, the stop matcher, the hop onto the event loop."""
from layer_metrics.ttft_engine_ms import mean_ms


def read(art):
    return mean_ms(art, "quorum_tpu_first_token_backend_seconds")
