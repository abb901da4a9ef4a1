"""A configuration file held to what its source publishes.

``configs/published/<model>.json`` is ``{"source", "config"}``: the values of
the source's own ``config.json``. A configuration of BENCHMARK.json is held
to the file whose ``source`` is its own: every value as published, but for
the keys its ``reduced`` lists, each of which differs, has its reason in the
file's ``reduced_why``, and is no width. ``num_experts`` and ``vocab_size``
may be this chip's share of a layer that several chips hold: then the file
states that ``deployment``, and the key's ``reduced_why`` the published count.
A published list or group (per-layer kinds, rope parameters) is held where
the file carries it.
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
PUBLISHED = os.path.join(HERE, "configs", "published")
WIDTHS = ("num_experts_per_tok", "sliding_window")
SHARES = ("num_experts", "vocab_size")


def is_width(key: str) -> bool:
    return (key.endswith(("_dim", "_rank")) or "hidden_size" in key
            or "intermediate" in key or key in WIDTHS)


def published_for(source: str, directory: str = PUBLISHED):
    """The published values of ``source``; None where no file has them."""
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            found = json.load(f)
        if found["source"] == source:
            return found["config"]
    return None


def problems(entry: dict, data: dict, directory: str = PUBLISHED) -> list:
    """Every way the configuration file ``data`` of BENCHMARK.json's
    ``entry`` departs from its source."""
    published = published_for(entry["source"], directory)
    if published is None:
        return [f"no file under configs/published/ has the source "
                f"{entry['source']!r}"]
    reduced, why = entry["reduced"], data.get("reduced_why", {})
    out = [f"{key} is a width and is in reduced"
           for key in reduced if is_width(key)]
    for key, value in published.items():
        if key in reduced:
            if key not in data or data[key] == value or not why.get(key):
                out.append(f"{key} is in reduced: it has to differ from the "
                           f"published {value!r} and have its reduced_why")
            elif key in SHARES and not (
                    data.get("deployment") and str(value) in why[key]):
                out.append(f"{key} is a share of the published {value!r}: "
                           "the file states the deployment, and the "
                           "reduced_why the published count")
        elif key not in data:
            if not isinstance(value, (list, dict)):
                out.append(f"{key}, published {value!r}, is left out and is "
                           "not in reduced")
        elif data[key] != value:
            out.append(f"{key} is {data[key]!r}, published {value!r}, and "
                       "is not in reduced")
    return out
