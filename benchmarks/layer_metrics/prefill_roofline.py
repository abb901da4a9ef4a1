"""Kernels: the least time the traced interval's prefill executions could
take over their measured device time, in %. Per execution, by
cost_model.prefill against peaks.json: the matrix and attention products of
its rows (read off the trace, prefill_ms_per_ktok.known_executions) or one
streaming of every member's weights, whichever takes longer. At the published
widths an int8 execution of up to 256 rows is bound by the weights' bytes and
a 512-token segment by its operations (PERF.md section 3)."""
import cost_model
from layer_metrics import prefill_ms_per_ktok


def read(art):
    known = prefill_ms_per_ktok.known_executions(art)
    if not known:
        return None
    grid = art["traffic"]["grid"]
    mean_prompt = sum(p for p, _ in grid) / len(grid)
    model = cost_model.for_config(art["config"])
    least = 0.0
    for rows, count, _ in known:
        ops, byts = model.prefill(art["config"], rows, mean_prompt, 1)
        least += count * model.least_seconds(ops, byts, art["config"],
                                             art["peaks"])
    return 100.0 * least / sum(sec for *_, sec in known)
