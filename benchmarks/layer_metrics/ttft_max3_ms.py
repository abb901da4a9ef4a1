"""Load generator's view of the tail where the window holds too few
requests for a 90th percentile: the third largest time-to-first-token of
the window's requests."""
from e2e import kth_largest, layer_records, ttft_ms


def read(art):
    return kth_largest(ttft_ms(layer_records(art)), 3)
