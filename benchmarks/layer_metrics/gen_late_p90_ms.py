"""Load generator: 90th percentile of (time the request's bytes were written
- time it was due), over the window's requests. A starved generator must not
read as a fast server."""
from e2e import layer_records, percentile


def read(art):
    late = [(r["sent"] - r["due"]) * 1000.0 for r in layer_records(art)
            if r.get("sent") is not None]
    return percentile(late, 0.9)
