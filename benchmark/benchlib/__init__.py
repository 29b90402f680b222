"""The benchmark's own library: wire client, load generator, trace
reduction, peaks, needed bytes. Nothing here names a cell, a statement
or a metric; those are data files found by name (see ../README.md)."""
