import os

# the benchmark's own tests run on the CPU; no test here describes a TPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")
