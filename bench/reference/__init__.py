"""Plain references: straightforward jax.numpy that imports nothing of the
program under test and takes nothing it made."""
