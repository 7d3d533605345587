"""The benchmark's own code: traffic, load generation, serving set-up,
the plain reference, the count functions, the trace reduction and the
table of peaks.  Nothing here is imported by the program."""
