"""What the plain references share: float32 contractions at the highest
matmul precision, the lower-precision stand-in the control computes in, and
per-leaf norms. Nothing here imports the program."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def exact(x):
    """The reference's operand treatment: none."""
    return x


def fp8_operand(x):
    """The control's: every matmul/conv operand rounded to float8 (e4m3,
    scaled per tensor to its largest magnitude), the step below bfloat16.
    Straight-through, so the backward pass sees the same rounded operands
    and cotangents stay float32. (Rounding the cotangents to float8 as well
    read the same on the ResNet cell: PERF.md, PR 24.)"""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    r = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(r - x)


OPERANDS = {"float32": exact, "fp8": fp8_operand}


def einsum(spec, a, b, q=exact):
    return jnp.einsum(spec, q(a), q(b), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def leaf_norms(tree):
    """{name: l2 norm as a float32 scalar array} of a flat dict."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}
