"""Device time per epoch of the operations the compiled step keeps under
``jax.named_scope("lowbit")``: ``quantize`` and ``dequantize`` of
``core/quantization.py`` on either path, with the noise draw, the kernels or
the jnp pack and unpack, and the casts of scale and zero. Mean over the
cell's chips."""

from bench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "lowbit")
