"""Open-loop traffic from a mix file (``traffic/<name>.json``) and a seed.

Every seed gets the same prompt lengths, output lengths and
inter-arrival gaps - the quantiles of the mix's distributions at
``(i + 0.5) / n`` - in the same shuffled order.  The seed draws the
prompts' token ids (and, in the harness, the weights), which change no
timing: two seeds ask for the same work at the same times, so a
difference between their runs is the system's and not the draw's.  A
seed-dependent order was tried first: on yi6b.prefill the time to
first token's 90th percentile then spread by 56-127% between seeds
(quartiles over median, six seeds), against 1-11% between two runs of
one seed, because at 80% of the knee the tail is set by where the long
prompts fall among the bursts.

A mix file holds:

* ``arrival`` - ``"poisson"``, the one arrival process so far;
* ``rate_rps`` - mean arrival rate of the Poisson stream (req/s);
* ``prompt_tokens`` / ``output_tokens`` - ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}``;
* ``due_at_start`` - requests due at time 0 besides the stream: a
  number, or ``"n_slots"`` for as many as the engine has slots;
* ``drain_s`` - how long the harness keeps stepping after the window
  for the window's requests to finish;
* ``check_requests`` - how many finished requests the output check
  compares with the reference (the longest among them);
* ``sessions`` (optional) - ``{"tenants", "system_prompt_tokens",
  "tenant_zipf"}``: each tenant has one system prompt, its token ids
  drawn from the seed, and each request's prompt is its tenant's system
  prompt followed by a turn of ``prompt_tokens``.  The tenant of each
  arrival follows a Zipf law over the tenants (exponent
  ``tenant_zipf``), as mid-quantiles in the one fixed order like the
  sizes and gaps, so two seeds send the same tenants at the same times.
  A mix without it draws exactly what it drew before sessions existed.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

MIX_DIR = Path(__file__).with_name("traffic")
# stream requests beyond rate x window, so the window never runs dry
STREAM_MARGIN = 1.3
ORDER_SEED = 0                 # the one shuffle of sizes and gaps


@dataclass
class Arrival:
    due: float                 # seconds after the window opens
    prompt: List[int]
    max_new: int
    tenant: str = "default"


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def rng_for(seed: int) -> np.random.Generator:
    """Any whole number, negative or beyond 64 bits, is a valid seed."""
    return np.random.default_rng(seed % 2**64)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as integers."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def zipf_quantiles(n_tenants: int, exponent: float, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a Zipf law over ``n_tenants`` tenants
    (tenant i weighs ``(i + 1) ** -exponent``): tenant indices."""
    w = np.arange(1, n_tenants + 1, dtype=float) ** -exponent
    cdf = np.cumsum(w) / w.sum()
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u, side="right"), n_tenants - 1)


def n_due_at_start(mix: dict, n_slots: int) -> int:
    v = mix.get("due_at_start", 0)
    return n_slots if v == "n_slots" else int(v)


def schedule(mix: dict, seed: int, seconds: float, n_slots: int,
             vocab: int) -> List[Arrival]:
    """The arrivals of one run, sorted by due time."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    order = np.random.default_rng(ORDER_SEED)
    n0 = n_due_at_start(mix, n_slots)
    n_stream = math.ceil(STREAM_MARGIN * mix["rate_rps"] * seconds)
    n = n0 + n_stream
    plen = order.permutation(quantiles(mix["prompt_tokens"], n))
    olen = order.permutation(quantiles(mix["output_tokens"], n))
    u = (np.arange(n_stream) + 0.5) / n_stream
    gaps = order.permutation(-np.log1p(-u) / mix["rate_rps"])
    rng = rng_for(seed)
    due = np.concatenate([np.zeros(n0), np.cumsum(gaps)])
    sessions = mix.get("sessions")
    if sessions is None:
        return [Arrival(float(due[i]),
                        rng.integers(0, vocab, int(plen[i])).tolist(),
                        int(olen[i]))
                for i in range(n)]
    tenant = order.permutation(zipf_quantiles(
        sessions["tenants"], sessions["tenant_zipf"], n))
    system = [rng.integers(0, vocab, sessions["system_prompt_tokens"])
              .tolist() for _ in range(sessions["tenants"])]
    return [Arrival(float(due[i]),
                    system[tenant[i]]
                    + rng.integers(0, vocab, int(plen[i])).tolist(),
                    int(olen[i]), f"tenant{tenant[i]}")
            for i in range(n)]
