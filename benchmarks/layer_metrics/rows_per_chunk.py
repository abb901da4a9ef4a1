"""Scheduler and admission: rows advanced per decode chunk over the window,
``decode_busy_rows_total`` / ``decode_chunks_total`` between the two
scrapes."""


def read(art):
    chunks = (art["m1"].get("quorum_tpu_engine_decode_chunks_total", 0.0)
              - art["m0"].get("quorum_tpu_engine_decode_chunks_total", 0.0))
    rows = (art["m1"].get("quorum_tpu_engine_decode_busy_rows_total", 0.0)
            - art["m0"].get("quorum_tpu_engine_decode_busy_rows_total", 0.0))
    return rows / chunks if chunks > 0 else None
