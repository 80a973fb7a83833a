"""One reader per per-layer metric: ``read(record)`` returns the number, or
None where the run holds nothing for it to read."""
