"""Shared lookups for the per-layer metric readers."""
from __future__ import annotations


def module_seconds(red, name: str):
    """(device seconds, runs) of the compiled program ``name`` in the
    traced window, or None where the trace holds none."""
    if red is None:
        return None
    secs = sum(v for k, v in red.module_s.items() if k.split("(")[0] == name)
    runs = sum(v for k, v in red.module_calls.items() if k.split("(")[0] == name)
    return (secs, runs) if runs else None
