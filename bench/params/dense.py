"""Weight builder of the dense decoder family, found by the family's name
(`bench.cell.weights_for`): seeded CLAQ-layout weights, `bench/weights.py`."""
from bench.weights import served_params  # noqa: F401
