"""``market4_diversified`` on the program: the diversified market, through the adapter that
the four-pool market configurations share."""
from chipbench.market_program import Program, control  # noqa: F401
