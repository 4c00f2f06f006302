import os

# the benchmark's tests run on the CPU; only the benchmark itself holds
# the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
