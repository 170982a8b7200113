"""The benchmark's own yardstick: traffic generation, statistics, peaks,
operation and byte counts, the xplane reduction and the manifest loader.
Only ``sut.py`` imports the program under test."""
