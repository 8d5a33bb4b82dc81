"""The on-chip benchmark's yardstick: traffic generation, the closed-loop
driver and its window arithmetic, the profiler-trace reduction, the
operation and byte counts per kernel and per step, the table of chip
peaks, the plain float32 references and the output comparison.

Nothing here imports the program at module import time; ``run.py``
puts the checkout's ``src`` on the path and hands the program in."""
