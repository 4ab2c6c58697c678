"""The chip benchmark of the spiking-network simulator.

One command, ``python3 -m chipbench.run``, runs one cell of
``BENCHMARK.json`` on the chip.  Everything that belongs to one
configuration, traffic mix, mode, metric or reference lives in a file of
its own under this directory and is found by the name the cell gives.
"""
