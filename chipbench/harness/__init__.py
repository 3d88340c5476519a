"""The benchmark's yardstick: cell loading, peaks, work counts, trace
reduction, traffic generation and the runner of one cell. Nothing here is
imported by the program under test."""
