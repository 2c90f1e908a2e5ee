"""Per-layer metrics: ``<name>.json`` says what the metric is and how it is
read (a program span, or a reader module here with ``read(ctx, **args)``).
``ctx`` holds ``events`` (perfbench.trace.extract), ``steps`` traced,
``throughput`` (samples/s/chip of this run), ``chips``, ``config`` and
``peaks``. A reader that finds nothing to read returns None."""
