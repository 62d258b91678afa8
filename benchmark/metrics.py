"""Metric values from a run's observations.  A metric is a data file,
``metrics/<name>.json`` = {"reader": <file in readers/>, "args": {...}};
the reader returns None where it finds nothing to read, and the metric is
then left out of the line (never 0 for a share of a roofline or a peak)."""

from __future__ import annotations

import os

from benchmark import harness


def read(name: str, obs: dict):
    spec = harness.load_json(os.path.join(harness.BENCH, "metrics",
                                          name + ".json"))
    reader = harness.load_module("readers/" + spec["reader"] + ".py")
    value = reader.read(obs, **spec.get("args", {}))
    return None if value is None else float(value)

