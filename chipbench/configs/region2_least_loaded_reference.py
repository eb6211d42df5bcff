"""Plain reference for ``region2_least_loaded``: the exact expected counts
of a window from the two regions' Markov chain.  Nothing of the program is
imported."""
from chipbench.region_chain import compare  # noqa: F401
