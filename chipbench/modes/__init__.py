"""Traffic modes: how a cell's calls drive the model and how their outputs
are checked.  A traffic file names its mode; ``chipbench/modes/<mode>.py``
defines ``Mode``, which the harness drives through ``warm_up``, ``call``
(the network-steps one timed call ran), ``spike_totals``, ``program_hlo``
(the optimized HLO text of the programs the calls ran, for a traced run's
readings by scope), ``release`` and ``check``."""
