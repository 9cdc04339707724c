from dag_rider_tpu.utils.metrics import Metrics
from dag_rider_tpu.utils.slog import NOOP, EventLog, capture, stdlib_sink

__all__ = ["Metrics", "NOOP", "EventLog", "capture", "stdlib_sink"]
