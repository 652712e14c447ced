"""Workload kinds: one module each, found by the ``kind`` a traffic file names."""
