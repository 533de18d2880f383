"""The one general generator of token-serving traffic.

Parameters (``traffic/<mix>.json``):

- ``arrival``: ``{"kind": "closed", "callers": N}`` — N callers, each sends
  its next request when its last one completes; or ``{"kind": "poisson",
  "rate_per_s": r}`` — an open loop, requests due at seeded exponential gaps
  whether or not earlier ones have finished (``"burst": {"every_s", "size"}``
  adds ``size`` simultaneous arrivals every ``every_s`` seconds).
- ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "fixed", "value"}``.
- ``pool``: how many distinct (prompt length, output length) pairs there are.
  The pairs are the distribution's quantile midpoints, paired by a fixed
  shuffle: EVERY seed gets the same multiset of sizes. The seed decides the
  order in which they are sent (a fresh permutation of the pool per cycle)
  and the tokens.
- ``strata`` (default 1): the pool, sorted by prompt length, is cut into this
  many equal strata, and every run of ``strata`` consecutive requests takes
  one pair from each (which one, and in what order, from the seed). Any
  stretch of the stream then holds nearly the same prefill work whatever the
  seed, so a window's work does not depend on which pairs the order put in it.
- ``max_total_len``: a pair whose sum exceeds it has its output shortened.
- ``shared_prefix``: ``null``, or ``{"count", "length", "share"}`` — a pool of
  ``count`` seeded prefixes of ``length`` tokens; a ``share`` of the requests
  (by position in the pool) start with one of them, inside their prompt length.
- ``ramp_s``: seconds of traffic before the measured window opens (set-up).
- ``temperature``: 0 for greedy.

The program receives only the generated requests.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_PAIRING_SEED = 20260927  # the fixed shuffle that pairs prompt and output sizes


def _quantile_lengths(spec: dict, n: int) -> list:
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        v = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def plan(params: dict, seed: int, config: dict) -> dict:
    n = int(params["pool"])
    prompts = _quantile_lengths(params["prompt_len"], n)
    outputs = _quantile_lengths(params["output_len"], n)
    pairing = np.random.default_rng(_PAIRING_SEED).permutation(n)
    limit = int(params["max_total_len"])
    sizes = []
    for i in range(n):
        p, o = prompts[i], outputs[int(pairing[i])]
        sizes.append((p, max(1, min(o, limit - p))))
    sp = params.get("shared_prefix")
    strata = int(params.get("strata", 1))
    if n % strata:
        raise ValueError(f"pool {n} is not a multiple of strata {strata}")
    return {"sizes": sizes, "seed": int(seed), "arrival": params["arrival"],
            "strata": strata,
            "ramp_s": float(params.get("ramp_s", 0.0)),
            "vocab": int(config["vocab_size"]),
            "temperature": float(params.get("temperature", 0.0)),
            "shared_prefix": sp}


def describe(p: dict) -> dict:
    pl = sorted(s[0] for s in p["sizes"])
    ol = sorted(s[1] for s in p["sizes"])
    mid = len(pl) // 2
    return {"generator": "requests", "arrival": p["arrival"],
            "pool": len(p["sizes"]),
            "prompt_len": {"min": pl[0], "median": pl[mid], "max": pl[-1],
                           "mean": sum(pl) / len(pl)},
            "output_len": {"min": ol[0], "median": ol[mid], "max": ol[-1],
                           "mean": sum(ol) / len(ol)}}


def stream(p: dict):
    """Endless ``(index, prompt_tokens, output_len)``: cycle after cycle, each
    a seeded order of the whole pool (one pair of every stratum in each run
    of ``strata`` requests), tokens drawn from the seed."""
    rng = np.random.default_rng([p["seed"], 1])
    sp = p["shared_prefix"]
    prefixes = None
    if sp:
        prng = np.random.default_rng([p["seed"], 2])
        prefixes = prng.integers(0, p["vocab"], (int(sp["count"]),
                                                 int(sp["length"])), np.int32)
    n, index = len(p["sizes"]), 0
    by_prompt = sorted(range(n), key=lambda j: p["sizes"][j])
    per = n // p["strata"]
    strata = [by_prompt[k * per:(k + 1) * per] for k in range(p["strata"])]
    while True:
        picks = [rng.permutation(stratum) for stratum in strata]
        order = [int(j) for r in range(per)
                 for j in rng.permutation([pk[r] for pk in picks])]
        for j in order:
            plen, olen = p["sizes"][int(j)]
            toks = rng.integers(0, p["vocab"], plen, dtype=np.int32)
            if prefixes is not None and (int(j) % 100) < 100 * sp["share"]:
                pre = prefixes[int(rng.integers(0, len(prefixes)))][:plen - 1]
                toks[:len(pre)] = pre
            yield index, toks, olen
            index += 1


def due_times(p: dict, horizon_s: float) -> list:
    """Open loop only: the seconds (from the start of traffic) at which each
    request of :func:`stream` is due, up to ``horizon_s``."""
    arr = p["arrival"]
    if arr["kind"] != "poisson":
        raise ValueError("due_times is for an open loop")
    rng = np.random.default_rng([p["seed"], 3])
    t, out = 0.0, []
    while t < horizon_s:
        t += float(rng.exponential(1.0 / arr["rate_per_s"]))
        out.append(t)
    burst = arr.get("burst")
    if burst:
        k = 1
        while k * burst["every_s"] < horizon_s:
            out.extend([k * burst["every_s"]] * int(burst["size"]))
            k += 1
    return sorted(x for x in out if x < horizon_s)
