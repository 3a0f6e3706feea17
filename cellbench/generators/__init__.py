"""Traffic generators, one module each, found by a mix's ``generator``
key (``cellbench.loadgen.generator``).  An adapter says which functions
it calls on the one its mix names."""
