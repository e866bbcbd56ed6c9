"""The plain reference: gradient boosting's arithmetic in straightforward
jax.numpy and float32, with no kernel, no binning and no row permutation.
It imports nothing of the program and takes nothing the program made but
the trees under comparison."""
