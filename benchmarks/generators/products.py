"""Traffic of the matrix cells: callers that repeat one product.

Parameters (``traffic/<mix>.json``): ``callers`` — how many products are in
flight at once (1: each product ends before the next starts).
"""

from __future__ import annotations


def plan(params: dict, seed: int, config: dict) -> dict:
    callers = int(params.get("callers", 1))
    if callers != 1:
        raise ValueError("products: only one caller is implemented")
    return {"callers": callers, "seed": int(seed)}


def describe(p: dict) -> dict:
    return {"generator": "products", "callers": p["callers"]}
