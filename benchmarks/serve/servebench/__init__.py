"""On-chip serving benchmark: traffic, timing, trace reduction, the plain
reference and the yardstick (peaks, operation and byte counts).

Nothing here imports the program at module level; ``harness`` and
``weights`` reach it only when a run drives it.
"""
