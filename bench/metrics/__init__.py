"""Per-layer metric readers, one file each, found by the metric's name.

Each module states its ``LAYER``, the end-to-end metric it ``MOVES``,
optionally a device-operation ``PATTERN`` for the trace reduction, and
``read(obs)``, which returns the metric or None where the run gave it
nothing to read."""
