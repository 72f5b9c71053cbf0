"""Rounding to bfloat16 for the controls, done on the bits.

A float32 -> bfloat16 -> float32 round trip written with `astype` may be
removed by the compiler, which is free to keep excess precision; integer
arithmetic on the bits cannot be. Round to nearest, ties to even."""
import jax
import jax.numpy as jnp


def round_bf16(x):
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
