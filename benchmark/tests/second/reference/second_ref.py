"""The plain reference of the test's second family: the same equations
as the dense decoder's, so it takes them from ``llama_ref`` (a
reference module may lean on another; neither imports the program).
What makes it a module of its own is that a configuration file names
it: the harness finds it as a file."""

from benchmark.reference.llama_ref import (  # noqa: F401
    layer_weights, outer_weights, seed_key, served_gaps, sizes)
