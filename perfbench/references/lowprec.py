"""Fake lower precisions for the controls of ``correct``: a tensor is
scaled along `axes` to the format's range, rounded to the format, and
brought back to float32, so that the reference's own arithmetic carries the
format's rounding and nothing else."""

from __future__ import annotations

import jax.numpy as jnp


def fake(x, axes, kind: str):
    """`x` as `kind` ("int8": symmetric, 127 steps; "fp8": e4m3, 3
    mantissa bits) would hold it, one scale for each slice along `axes`."""
    top = {"int8": 127.0, "fp8": 448.0}.get(kind)
    if top is None:
        raise ValueError(f"unknown control precision {kind!r}")
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
