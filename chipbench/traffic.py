"""The one traffic generator. A traffic file (`traffic/<name>.json`)
holds parameters only; its "kind" picks the schedule below.

kind "fit": back-to-back `fit()` calls of `num_iters` iterations each on
one problem built in set-up.
"""
from __future__ import annotations

KINDS = {"fit": ("num_iters",)}


def check(traffic: dict) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of "
                         f"{sorted(KINDS)}")
    missing = [k for k in KINDS[kind] if k not in traffic]
    if missing:
        raise ValueError(f"{kind} traffic lacks {missing}")
