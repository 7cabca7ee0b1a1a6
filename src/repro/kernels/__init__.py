"""Pallas TPU kernels for the compute hot-spots: compiled on a TPU by
``chip_smoke.py`` at the shapes in ``cases.py``, and validated in
interpret mode against pure-jnp oracles by tests/test_kernels.py."""
