"""The benchmark of tpu-rados: one command runs one cell of
BENCHMARK.json once on the chip (`python -m benchmark.run`).

Everything that decides a number lives here and not in the program:
traffic generation, the metric arithmetic, the table of peaks, the
kernel's bytes, the reduction of a device trace, the plain reference
and the comparison that decides `correct`.  From the program it takes
the system under test (`Cluster`, `Rados`/`ioctx`), the warm-up entry
`ECBatchQueue.apply`, the perf-counter and tracer dumps, and kernel
names."""
