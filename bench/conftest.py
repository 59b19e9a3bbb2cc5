import os

# The benchmark's own tests run on the CPU backend, at small sizes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
