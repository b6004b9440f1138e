"""The chip's published peaks, from ``peaks.json``, keyed by the
``device_kind`` that JAX reports.  A device missing from the table is an
error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def of(device: dict) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    kind = device["kind"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]
