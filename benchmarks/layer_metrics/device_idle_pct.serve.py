"""layer: device. Share of the traced window in which no operation ran on the
chip (the worst chip of a mesh). Source: device trace."""

from benchmarks.trace_reduce import worst_idle_pct as read  # noqa: F401
