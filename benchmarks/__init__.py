"""The on-chip benchmark (BENCHMARK.json's ``paths``). ``run.py`` is the command;
everything that belongs to one cell, configuration or metric is a file that
``run.py`` finds by name. See README.md."""
