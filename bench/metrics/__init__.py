"""One reader per metric, ``<name>.py`` with ``read(window)`` returning the
value or None where the run gives it nothing to read."""
