"""One reader a per-layer metric, found by the metric's name:
``read(run)`` returns the value, or None where it finds nothing to
read (the harness then leaves the metric out of the line)."""
