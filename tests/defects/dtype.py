"""Defect: dtype drift — a strong ``np.float32`` scalar widening bf16
math (the array-upcast finding), and an f64 variant for x64 mode.

``np.float32(2.0)`` is strong-typed (NumPy scalars don't weak-type
like Python floats), so the bf16 input is converted up before the
multiply — exactly the promotion that silently doubles a model's
memory traffic."""

import jax.numpy as jnp
import numpy as np

from repro.analysis.entrypoints import Built, EntryPoint


def _widened(x):
    return (x * np.float32(2.0)).sum()


def _f64(x):
    return (x.astype(jnp.float64) * 2.0).sum()      # lint: dtype-ok


def _build(suite: str) -> Built:
    x = jnp.ones((8, 8), jnp.bfloat16)
    return Built(fn=_widened, args=(x,))


def build_f64(suite: str = "8core") -> Built:
    """Only meaningful under ``jax.enable_x64`` — with
    x64 off, jax canonicalises the cast back to f32."""
    return Built(fn=_f64, args=(jnp.ones(16, jnp.float32),))


ENTRY = EntryPoint("defect.dtype", _build, suites=("8core",))
ENTRY_F64 = EntryPoint("defect.dtype-f64", build_f64, suites=("8core",))
