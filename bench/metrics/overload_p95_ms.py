"""95th-percentile due-to-completion latency of a cell offered more than it
serves (ms): recorded past the knee, where it swings with the backlog."""

from latency_p95_ms import read  # noqa: F401
