"""``device.idle_share`` in the cells whose read metric is the median, ``solve_p50_ms``."""
from bench import spec

read = spec.metric_reader("device.idle_share").read
