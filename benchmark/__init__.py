"""The benchmark: one data-driven command (run.py) and its yardstick."""
