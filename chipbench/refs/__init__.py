"""Plain references in straightforward jax.numpy, independent of the program
under test: the benchmark's own definition of a correct answer."""
