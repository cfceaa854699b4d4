"""The chip benchmark of diverse k-NN search: one cell per run, driven by
``BENCHMARK.json`` and the files under this directory (see ``bench.run``).

Importing this package imports no JAX: the reference's worker processes
import it too, and must not claim the chip."""
