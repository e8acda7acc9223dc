"""Jitted wrapper for the parity kernel."""
from __future__ import annotations

import functools

import jax

from ..common import striped_rows
from . import ref
from .parity import stripe_parity_striped


@functools.partial(jax.jit, static_argnames=("stripe_width", "use_pallas", "interpret"))
def stripe_parity(
    lanes2d: jax.Array,
    stripe_width: int = 4,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """uint32[n_stripes, L] XOR parity of a (n_blocks, L) lane view."""
    if not use_pallas:
        return ref.stripe_parity(lanes2d, stripe_width)
    par = stripe_parity_striped(striped_rows(lanes2d, stripe_width),
                                interpret=interpret)
    return par.reshape(par.shape[0], lanes2d.shape[1])
