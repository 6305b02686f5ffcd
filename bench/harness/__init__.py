"""The benchmark's yardstick: set-up, traffic driving, spans, the plain
reference, the comparison that decides ``correct``, and the reduction of
profiler traces to metrics.  Nothing here imports the program under test
except :mod:`harness.cell`, which drives it through its front door."""
