"""The benchmark's own yardstick: traffic, statistics, operation counts,
peaks, trace reduction and the comparison that decides ``correct``.

Nothing in this package imports the program except ``served.py`` (the
system under test and the wrappers around its calls) and ``weights.py``
(which lays the seeded weights out in the pytree the program takes).
"""
