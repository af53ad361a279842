"""The benchmark's yardstick: file loading, the replica-group loop, the
window, the statistics, the trace reduction, the peaks table and the
comparison that decides ``correct``."""
