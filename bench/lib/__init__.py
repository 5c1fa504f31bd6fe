"""The benchmark's yardstick: peaks, the Pallas calls of a compiled
program, trace reduction and the comparison that decides ``correct``."""
