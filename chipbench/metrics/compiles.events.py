"""Executables the program built inside the measured window (XLA
compiles or persistent-cache loads): its ``repro.compiled`` markers."""
from chipbench.spans import compiles


def read(run):
    return compiles(run)
