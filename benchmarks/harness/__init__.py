"""The benchmark's yardstick: data from the seed, the measured window, the
reduction from spans and the device trace to metrics, the table of peaks, the
FLOP count and the comparison that decides `correct`. See benchmarks/run.py."""
