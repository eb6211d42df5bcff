"""Plain reference for ``market4_diversified``: the exact expected counts of a
window from the market's Markov chain, shared by the four-pool market
configurations.  Nothing of the program is imported."""
from chipbench.market_chain import compare  # noqa: F401
