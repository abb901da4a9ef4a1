"""KV manager: peak bytes in use on the fullest device of the mesh, from
``memory_stats()["peak_bytes_in_use"]`` through /health after the window."""


def read(art):
    peak = art["memory_peak_bytes"]
    return peak / 1e9 if peak else None
