"""``loadgen.lag_p95_ms`` in the cells whose read metric is the median, ``solve_p50_ms``."""
from bench import spec

read = spec.metric_reader("loadgen.lag_p95_ms").read
