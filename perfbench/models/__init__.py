"""Glue between a configuration file and the program under test: how the
program is told to build the model, and how the benchmark's seeded weights
are laid out as the program's parameter tree.  One module per family, named
by a configuration's ``model`` key."""
