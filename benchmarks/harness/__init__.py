"""The benchmark's own yardstick: traffic, statistics, operation counts,
peaks, trace reduction and the comparison that decides ``correct``.

Nothing in this package imports the program except ``served.py`` (the
system under test and the wrappers around its calls), and nothing in it
names a key of a model's shape: what the benchmark knows of an architecture
is in ``families/<family>.py`` and ``references/<reference>.py``, found by
the two names in the configuration's file (``spec.load_family``,
``spec.load_reference``).
"""
