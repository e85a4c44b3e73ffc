"""Pallas interpret mode, decided by the backend JAX runs on."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a ``pallas_call``: automatic (``None``) means on
    for the CPU backend, which the tests use, and off on a TPU.  Asking
    for interpret mode on a TPU is an error: it would run the kernel body
    as slow emulation and hide whether the chip's compiler accepts it."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is refused on a TPU")
    return interpret
