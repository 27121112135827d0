"""Plain references that the program is held against (no flax, no kernels)."""
