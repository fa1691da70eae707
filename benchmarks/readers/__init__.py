"""One small reader per source of a metric. ``read(run, **arguments)`` takes
the number from what the run recorded (``run`` is ``run.py``'s ``Run``) and
returns ``None`` where there is nothing to read; the arguments come from the
metric's JSON file."""
