"""jit'd wrapper for the fused RMSNorm kernel, with a custom VJP.

A ``pallas_call`` has no reverse-mode rule, so the backward pass is
the VJP of the jnp reference (XLA fuses it); the forward stays the
kernel.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm_kernel
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, weight, eps, interpret):
    return rmsnorm_kernel(x, weight, eps=eps, interpret=interpret)


def _rmsnorm_fwd(x, weight, eps, interpret):
    return _rmsnorm(x, weight, eps, interpret), (x, weight)


def _rmsnorm_bwd(eps, interpret, res, dy):
    x, weight = res
    _, vjp = jax.vjp(functools.partial(rmsnorm_ref, eps=eps), x, weight)
    return vjp(dy)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, weight, *, eps=1e-5, interpret=False):
    return _rmsnorm(x, weight, eps, interpret)
