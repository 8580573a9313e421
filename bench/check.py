"""The output check that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished (drawn from the seed, the longest always
in it) is run through the configuration's plain reference: each prompt
with the tokens the engine served after it, as one sequence.  At each
served position the number compared is how far the reference's logit
for the served token lies below the reference's best logit there; the
run's reading is the widest such gap.  Greedy serving picks the best
logit of its own bf16 computation, so the gap is rounding where the
program computes the model, and large where it does not.

The control (``control_gaps``) reads the same positions of the same
sequences, with the token that an fp8 computation of the reference puts
first in place of the served one: the nearest precision below the bf16
the configurations state.  ``checks/<cell>.json`` holds each cell's
limit and the readings it was set from.
"""
from __future__ import annotations

import functools
import importlib
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

MIN_BUCKET = 128


def reference(config: dict):
    """The configuration's plain reference, ``references/<name>.py``.

    Besides ``init_weights`` and ``logits`` it declares the model kind
    it computes, which the harness holds the program's configuration
    to: ``FIELDS``, the keys of the configuration file's ``model`` group
    that must equal the program configuration's attributes of the same
    name (a group given as an object is compared key by key), and
    ``KIND``, program attributes and the values it computes them for.
    ``COUNTS`` names the module under ``bench/`` with that kind's
    operation and byte counts; ``counts`` when it names none."""
    return importlib.import_module(f"bench.references.{config['reference']}")


def counts(config: dict):
    """The operation and byte counts of the configuration's model kind,
    as its reference names them."""
    name = getattr(reference(config), "COUNTS", "counts")
    return importlib.import_module(f"bench.{name}")


def bucket(n: int) -> int:
    """Sequences are padded to a power of two, so the reference compiles
    for a few lengths and not for every one."""
    return max(MIN_BUCKET, 1 << (n - 1).bit_length())


def sample(flights: Sequence, seed: int, k: int) -> List:
    """Up to ``k`` finished flights: the one that served most tokens,
    and the rest drawn from the seed.  Where some flights started from
    a prefix copied out of a prefix cache and the draw holds none, the
    last one drawn gives way to one of those, drawn from the seed too,
    so that a wrongly copied prefix shows."""
    done = [f for f in flights if f.finished]
    if not done:
        return []
    longest = max(done, key=lambda f: (len(f.tokens), len(f.prompt)))
    rest = [f for f in done if f is not longest]
    rng = traffic.rng_for(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    out = [longest] + [rest[i] for i in sorted(pick)]
    adopted = [f for f in rest if f.adopted]
    if adopted and len(out) > 1 and not any(f.adopted for f in out):
        out[-1] = adopted[rng.integers(len(adopted))]
    return out


def _sequence(f):
    """Prompt + served tokens as one padded sequence, and per position
    the served token it predicts (-1 where none is compared)."""
    seq = list(f.prompt) + list(f.tokens[:-1])
    n = bucket(len(seq))
    toks = np.zeros((n,), np.int32)
    toks[:len(seq)] = seq
    target = np.full((n,), -1, np.int32)
    p0 = len(f.prompt) - 1
    target[p0:p0 + len(f.tokens)] = f.tokens
    return toks, target


def _gap_of(ref_logits, chosen, target):
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
    return jnp.where(target >= 0, best - got, -jnp.inf).max()


@functools.lru_cache(maxsize=None)
def _gap_fn(ref, model_key, control: bool):
    """Jitted: the widest gap at the compared positions of the served
    token, or (``control``) of the fp8 computation's first token."""
    m = dict(model_key)

    @jax.jit
    def fn(w, toks, target):
        lg = ref.logits(m, w, toks)
        chosen = (ref.logits(m, w, toks, fp8=True).argmax(-1)
                  .astype(jnp.int32) if control else jnp.maximum(target, 0))
        return _gap_of(lg, chosen, target)
    return fn


def make_weights(config: dict, seed: int, device=None):
    """The seed's weights, on ``device`` (the default device when None),
    in one jitted call."""
    m = config["model"]
    ref = reference(config)
    out = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(functools.partial(ref.init_weights, m),
                   out_shardings=out)(weight_key(seed))


def weight_key(seed: int):
    lo, hi = seed % 2**31, (seed // 2**31) % 2**31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _widest(config: dict, weights, flights, control: bool) -> float:
    fn = _gap_fn(reference(config), tuple(sorted(config["model"].items())),
                 control)
    gaps = [float(fn(weights, *map(jnp.asarray, _sequence(f))))
            for f in flights]
    return max(gaps) if gaps else float("nan")


def served_gap(config: dict, weights, flights) -> float:
    """Widest gap of a served token below the reference's best."""
    return _widest(config, weights, flights, control=False)


def control_gaps(config: dict, weights, flights) -> float:
    """Widest gap of the fp8 computation's first token below the
    reference's best, at the same positions."""
    return _widest(config, weights, flights, control=True)
