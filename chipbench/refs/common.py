"""Shared pieces of the plain references: sites, convs, folded batchnorm."""
from __future__ import annotations

import contextlib
import itertools
import math

import jax
import jax.numpy as jnp

SITE_PREFIX = "site."
_instances = itertools.count()


@contextlib.contextmanager
def site(kernel: str):
    """Everything traced inside belongs to one site served by ``kernel``.
    The scope is named ``site.<kernel>#<n>``, ``n`` telling sites apart."""
    with jax.named_scope(f"{SITE_PREFIX}{kernel}#{next(_instances)}"):
        yield


def fake_quant(a, bits: int | None, axes=None):
    """Symmetric rounding to ``bits`` bits: per tensor (``axes=None``) or
    per slice kept by reducing over ``axes``.  ``bits=None`` is identity."""
    if bits is None:
        return a
    q = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axes, keepdims=axes is not None),
                    1e-8) / q
    return jnp.clip(jnp.round(a / s), -q, q) * s


def conv(x, w, stride: int = 1, groups: int = 1, bits: int | None = None):
    """NHWC x HWIO convolution, SAME padding."""
    x = fake_quant(x, bits)
    w = fake_quant(w, bits, axes=(0, 1, 2))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def dense(x, w, b, bits: int | None = None):
    return fake_quant(x, bits) @ fake_quant(w, bits, axes=(0,)) + b


def bn(y, p):
    """Batchnorm folded to a per-channel affine."""
    return y * p["s"] + p["b"]


def relu(y):
    return jnp.maximum(y, 0.0)


def maxpool(x, k: int, stride: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, stride, stride, 1), "VALID")


def conv_w(key, kh, kw, cin, cout):
    """He-scaled normal weights, HWIO: a relu keeps the signal's size."""
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * math.sqrt(2.0 / (kh * kw * cin)))


def bn_p(key, c, lo=0.8, hi=1.2):
    """A folded batchnorm: scales drawn from [lo, hi), shifts N(0, 0.1),
    so the epilogue is exercised and not the identity."""
    ks, kb = jax.random.split(key)
    return {"s": jax.random.uniform(ks, (c,), jnp.float32, lo, hi),
            "b": 0.1 * jax.random.normal(kb, (c,), jnp.float32)}


def head_p(key, cin, cout):
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (cin, cout), jnp.float32)
            / math.sqrt(cin),
            "b": 0.1 * jax.random.normal(kb, (cout,), jnp.float32)}
