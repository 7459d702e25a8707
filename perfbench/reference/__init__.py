"""Plain references, one module a family of configurations.

A configuration file names its module as ``checks.reference``; the harness
then runs ``python -m perfbench.reference --workload <cell> --seed <n>`` once
the window has closed and every role is gone, and holds round 0's first loss,
as the worker logged it, to the module's ``first_loss(config, input_ids,
model_seed)``. A module imports nothing of the program and makes its own
weights from the seed. A later PR adds a module and a configuration that
names it, and edits nothing here.
"""
