"""Pallas TPU kernels (flash attention, RMSNorm, Mamba-2 SSD), their plain-XLA
forms (``xla_flash``, ``ssd_xla``), their pure-jnp oracles (``ref``) and the
differentiable wrappers the model calls (``ops``)."""
from __future__ import annotations

import jax


def check_interpret(interpret: bool) -> None:
    """Refuse the Pallas interpreter off the CPU backend.

    A kernel asked to interpret on an accelerator would run a Python-level
    emulation there and report no error, so on a TPU every kernel compiles
    natively; tests on the CPU ask for ``interpret=True`` explicitly."""
    if interpret and jax.default_backend() != "cpu":
        raise ValueError(
            f"interpret=True is for the CPU backend only; the "
            f"{jax.default_backend()!r} backend compiles Pallas kernels natively"
        )
