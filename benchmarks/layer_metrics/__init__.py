"""One reader per per-layer metric, each a file named after its metric and
found by that name (``run.load_reader``): ``LAYER``, ``UNIT``, ``MOVES`` and
``read(run)``, which returns ``None`` where it finds nothing to read."""
