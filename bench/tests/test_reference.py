"""The reference's Low-bit Module states its rounding: at 1 bit a row comes
back as its zero or its zero plus its scale, each rounded to
``scale_dtype``."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import reference as R


def test_one_bit_rows_take_the_rounded_zero_and_scale():
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 602)) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(1), h.shape)
    out = np.asarray(jax.jit(lambda h, u: R.quantize_roundtrip(
        h, u, 1, True, jnp.bfloat16))(h, u))
    x = np.asarray(h)
    lo, rng = x.min(1), x.max(1) - x.min(1)
    zero = lo.astype(ml_dtypes.bfloat16).astype(np.float32)
    top = zero + rng.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(out.min(1), zero)
    assert np.array_equal(out.max(1), top)
    assert np.all((out == zero[:, None]) | (out == top[:, None]))


def test_round_to_float32_changes_nothing():
    x = jax.random.normal(jax.random.PRNGKey(2), (1000,))
    assert np.array_equal(np.asarray(R.round_to(x, jnp.float32)), np.asarray(x))
