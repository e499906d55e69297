"""The benchmark's yardstick: loading cells by name, traffic generation,
the plain references and the comparison that decides ``correct``, the
reduction of profiler traces to device metrics, and the work counts that
rooflines divide by.  From the program under test it takes only the
system itself and its counters and kernel names."""
