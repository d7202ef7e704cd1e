"""The benchmark's yardstick: device checks, data and forest generators,
the plain reference, the trace reduction and the traffic drivers.

Nothing here is imported by the program under test; the program is
imported only by ``drivers`` (the system under test) and nowhere else.
"""
