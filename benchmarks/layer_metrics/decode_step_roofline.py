"""Kernels: the least time a decode step could take (cost_model.decode_step
at the rows per chunk the window ran and the mix's mean context, against
peaks.json; HBM-bound at these shapes) over the measured device time per
step, in %."""
import cost_model
from layer_metrics import decode_step_ms, rows_per_chunk


def mean_context(art) -> float:
    grid = art["traffic"]["grid"]
    return sum(p + c / 2.0 for p, c in grid) / len(grid)


def read(art):
    measured = decode_step_ms.read(art)
    rows = rows_per_chunk.read(art)
    if not measured or not rows:
        return None
    model = cost_model.for_config(art["config"])
    ops, byts = model.decode_step(art["config"], rows, mean_context(art))
    least = model.least_seconds(ops, byts, art["config"], art["peaks"])
    return 100.0 * least * 1000.0 / measured
