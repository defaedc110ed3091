"""The benchmark's own code: contract lookup, traffic plan, load
generator, statistics, the server child, the float32 reference, the
shape functions, the peaks table and the trace reduction. Nothing here
is imported by the program under test."""
