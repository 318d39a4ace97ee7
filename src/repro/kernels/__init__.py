# Pallas TPU kernels for the perf-critical compute layers, each with a
# pure-jnp oracle in ref.py and interpret=True validation in tests.
from .ssd_scan import ssd_chunk_intra  # noqa: F401
