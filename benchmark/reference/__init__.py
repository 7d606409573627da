"""Plain references: ``jax.numpy``, float32, no kernels, no compact path."""
