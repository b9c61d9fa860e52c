"""Chip benchmark of the served path: open-loop traffic on the host's
clock, per-layer readings from the program's counters and the device
trace, and a float32 reference that decides ``correct``."""
