"""Reader ``counter``: a number the program counted.

Parameter, in the metric's file: ``counter``, a key of the runner's
``facts["counters"]``.  The value is that counter as the runner returned it;
None where the runner returns no such counter, as a program without the
counted mechanism does.
"""
from __future__ import annotations


def read(spec: dict, facts: dict):
    """The metric's value from ``facts``, or None."""
    return facts.get("counters", {}).get(spec["counter"])
