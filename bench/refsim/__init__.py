"""The plain reference of both configurations, kept with the benchmark.

It imports nothing of the program under test, so later changes to the
program cannot move it.  Written for the benchmark, apart from the
program's code:

- ``traffic``: uniform random traffic from the definition of §IV.B;
- ``metrics``: throughput, latency and energy reduced from the final
  state with plain numpy;
- ``engine.run``: every cycle of the budget in one fixed-length scan, at
  the lane's natural sizes, with no early exit and no drain shortcut.

Frozen copies of the repository's modules, imports rewired (the program
has these too, so a fault in them would show on both sides):

- ``engine``'s cycle step and ``pack``: the scatter/segment formulation
  of ``core/simulator_ref.py``, written apart from the program's
  gather-style step in ``core/simulator.py``;
- ``topology``, ``routing``, ``constants``: the 4C4M system, its routing
  tables and the paper's constants;
- ``channel``, ``rates``, ``retx``, ``living``, ``dram``: the lossy
  channel's link tables, rate table, CRC hash, living-channel window
  update and the DRAM constants ``pack`` reads.
"""
