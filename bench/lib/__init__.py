"""Shared code of the on-chip benchmark: cell lookup, traffic, client,
statistics, FLOP and byte counts, trace reduction and the output check.
Nothing here imports the program under test (``src/repro``)."""
