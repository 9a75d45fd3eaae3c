"""``batcher.queue_wait_ms`` in the cells whose read metric is the median, ``solve_p50_ms``."""
from bench import spec

read = spec.metric_reader("batcher.queue_wait_ms").read
