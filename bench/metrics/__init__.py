"""Per-layer metric readers: ``<base>.py`` reads every metric named
``<base>`` or ``<base>.<family>``.  ``read(ctx, name)`` returns a number,
or None where the run holds nothing to read (the metric is then left out).
"""
