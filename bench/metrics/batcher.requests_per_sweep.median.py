"""``batcher.requests_per_sweep`` in the cells whose read metric is the median, ``solve_p50_ms``."""
from bench import spec

read = spec.metric_reader("batcher.requests_per_sweep").read
