"""Model step: device time of the decode programs in the traced interval
over the decode steps they ran (executions x steps per chunk)."""


def read(art):
    t = art["trace"]
    if not t or not t["programs"]["decode"]["count"]:
        return None
    p = t["programs"]["decode"]
    return p["seconds"] * 1000.0 / (p["count"] * art["config"]["decode_chunk"])
