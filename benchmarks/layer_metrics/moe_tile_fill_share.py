"""Model step: of the rows the expert products multiplied, the share that
were picks: the rise of ``moe_picks_held_total`` less the rise of
``moe_dropped_picks_total``, over the rise of ``moe_tile_rows_total``
between the window's scrapes, in percent. The engine counts the rows on the
device: tiles x 128 where the picks are grouped by expert into tiles, held
experts x counted rows where every held expert runs over every row. 12.5 is
what 16 picks in a tile of 128 read (a decode step of 128 rows over 32
experts, 4 picked): what a smaller tile or a ragged product would win. A
program without the counter (no expert layer, or an engine from before it)
reads nothing."""
from layer_metrics.expert_picks_held_share import HELD
from layer_metrics.prefill_decode_wait_share import delta

DROPPED = "quorum_tpu_engine_moe_dropped_picks_total"
ROWS = "quorum_tpu_engine_moe_tile_rows_total"


def read(art):
    rows, held, dropped = delta(art, ROWS), delta(art, HELD), \
        delta(art, DROPPED)
    if held is None or not rows or rows <= 0:
        return None
    return 100.0 * (held - (dropped or 0)) / rows
