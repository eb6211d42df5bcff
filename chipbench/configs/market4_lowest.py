"""``market4_lowest`` on the program: the lowest-price market, through the adapter that
the four-pool market configurations share."""
from chipbench.market_program import Program, control  # noqa: F401
