"""Plain float32 references: straightforward ``jax.numpy`` from the
published descriptions, independent of ``src/``. Each computes the loss,
the gradient and the LARS update of the first training steps from the
seed alone."""
